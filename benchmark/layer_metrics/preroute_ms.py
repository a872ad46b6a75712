"""Device milliseconds a round spends routing BEFORE the attention, in a
layer whose router reads the layer's input (SmallThinker): router scores
from the attention's normed input, their softmax, the top k, the
renormalised combine weights and which held experts each token reached,
forward and backward.  Ops under the program's ``dopt_preroute`` scope
(inside ``dopt_moe`` / ``dopt_route``, so ``route_ms`` and ``moe_ms`` hold
them too; a recompute counts here and in ``remat_ms`` both), busiest
chip.  Left out where the program has no such scope: a router after the
attention, or a program from before it."""

from benchmark.layer_metrics.local_ms import scoped_ms


def read(run):
    return scoped_ms(run, "dopt_preroute") or None
