"""Device milliseconds a round spends in the output head, forward and
backward: final RMS norm, logits a block of positions at a time,
log-softmax and the token loss.  Ops under the program's ``dopt_head``
scope, busiest chip."""

from benchmark.layer_metrics.local_ms import scoped_ms


def read(run):
    return scoped_ms(run, "dopt_head")
