"""Device milliseconds a round spends routing inside the expert layers,
forward and backward: router scores, top-k, the held experts' combine
weights and their application, without the expert matmuls.  Ops under
the program's ``dopt_route`` scope (inside ``dopt_moe``), busiest chip."""

from benchmark.layer_metrics.local_ms import scoped_ms


def read(run):
    return scoped_ms(run, "dopt_route")
