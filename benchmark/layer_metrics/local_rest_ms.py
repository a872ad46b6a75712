"""Device milliseconds a round of a decoder's local phase that none of
its named parts covers: the embedding and its gradient, the RMS norm
in front of each MLP and the residual add after it, the fleet's glue
around the model (the loss's and the counters' reductions) and
the bubbles inside the scoped ``while`` of the local steps, which covers
its body.  The time that ``local_ms`` reads (ops under ``dopt_local``)
less the union of ``dopt_attn`` (with the ``splash_mqa_*`` kernels, the
only ones whose names do not spell a scope out), ``dopt_moe``,
``dopt_mlp``, ``dopt_head``, ``dopt_update``, ``dopt_batch`` and
``dopt_eval``, so that ``local_ms`` = the union of those under it + this,
exactly, on the chip where ``local_ms`` is longest.  Left out where no
op carries ``dopt_qkv`` (no decoder, or a program from before its
scopes)."""

from benchmark.layer_metrics.attn_rest_ms import remainder_ms

CHILDREN = ("dopt_attn", "splash_mqa_", "dopt_moe", "dopt_mlp",
            "dopt_head", "dopt_update", "dopt_batch", "dopt_eval")


def read(run):
    return remainder_ms(run, ("dopt_local",), CHILDREN)
