"""Device milliseconds a round spends between the decoder attention's
projections and the attention itself, forward and backward: the rotary
embedding of q and k with the casts to the compute dtype around it (and
the queries' 1/sqrt(head_dim) scale for the fused kernel, which XLA
fuses into that pass) and, in a layer with per-head RMS norms, those
norms.  Float32 elementwise
passes.  Ops under the program's ``dopt_rope`` scope (inside
``dopt_attn``; a recompute counts here and in ``remat_ms`` both),
busiest chip.  Left out where the program has no such scope."""

from benchmark.layer_metrics.local_ms import scoped_ms


def read(run):
    return scoped_ms(run, "dopt_rope") or None
