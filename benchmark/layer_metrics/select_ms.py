"""Device milliseconds a round spends finding, for every query, the
k-th largest of its index scores and building the mask of the keys it
attends (exactly ``min(t + 1, topk)`` of them, ties to the lower
position): the counting passes of the bisection and the running count
that breaks ties.  Ops under the program's ``dopt_select`` scope (inside
``dopt_index``), busiest chip.  Bound by neither the MXU nor, much, by
HBM: passes over a block's scores.  Left out where the program has no
such scope."""

from benchmark.layer_metrics.local_ms import scoped_ms


def read(run):
    return scoped_ms(run, "dopt_select") or None
