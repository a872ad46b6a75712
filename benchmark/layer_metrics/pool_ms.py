"""Device milliseconds a round spends in the training step's 2x2
max-pools, forward and backward: ops under the program's ``dopt_pool``
scope, busiest chip.  It reads what XLA leaves under the scope: the
forward's (max, winner code) reduce with the bias add fused in front of
it, and what the backward's select keeps outside the weight-gradient
convolution it is fused into.  Evaluation pools without the scope
(``eval_ms`` has them).  0.0 on a program from before the scope."""

from benchmark.layer_metrics.local_ms import scoped_ms


def read(run):
    return scoped_ms(run, "dopt_pool")
