"""Device milliseconds a round spends in the decoder's dense layers' MLP
(gate, up, silu, down; without the layer's norm and residual add),
forward and backward.  Ops under the program's ``dopt_mlp`` scope,
busiest chip.  Left out where the program has no such scope, or no dense
layer."""

from benchmark.layer_metrics.local_ms import scoped_ms


def read(run):
    return scoped_ms(run, "dopt_mlp") or None
