"""Device milliseconds a round spends in the decoder attention's output
projection, forward and backward: in a layer with a per-head gate the
gate's sigmoid and its product with the attention output, then the
product with ``o``; in a layer with an indexer the product a run of
blocks at a time.  Ops under the program's ``dopt_oproj`` scope (inside
``dopt_attn``; a recompute counts here and in ``remat_ms`` both), busiest
chip.  Left out where the program has no such scope."""

from benchmark.layer_metrics.local_ms import scoped_ms


def read(run):
    return scoped_ms(run, "dopt_oproj") or None
