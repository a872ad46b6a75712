"""Device milliseconds a round spends in the decoder's expert layers,
forward and backward: router, the held experts' and the shared expert's
gated MLPs and the combine (the dense first layer's MLP is outside it).
Compute spent on tokens an expert was not routed is in here and not in
``program_mfu``'s operation count.  Ops under the program's ``dopt_moe``
scope, busiest chip."""

from benchmark.layer_metrics.local_ms import scoped_ms


def read(run):
    return scoped_ms(run, "dopt_moe")
