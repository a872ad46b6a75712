"""Device milliseconds a round spends in the decoder's shared expert (a
gated MLP every token takes, beside the routed experts), forward and
backward.  Ops under the program's ``dopt_shared`` scope (inside
``dopt_moe``), busiest chip.  Left out where the program has no such
scope, or no shared expert."""

from benchmark.layer_metrics.local_ms import scoped_ms


def read(run):
    return scoped_ms(run, "dopt_shared") or None
