"""Device milliseconds a round spends in the first convolution of the
reference CNNs over the stacked fleet, bias add included, in training and
in every evaluation of the round program: ops under the program's
``dopt_conv1`` scope, busiest chip.  It reads what XLA leaves under the
scope.  Where conv1 packs four workers a group (PERF.md §3, §6 PR 31)
that is the forward's convolution fusion, the weight-gradient fusion
(its root is the selection of the diagonal blocks, so it keeps conv1's
name and the update stays outside it), the bias-gradient sum, and the
evaluation's forward with the relayouts XLA puts behind it.  Where
conv1 keeps one group a worker, the weight-gradient fusion's root is
the update and goes by the update's name: the forward and the
bias-gradient sum are read.  0.0 on a program from before the scope
(there the forward is an unnamed fusion and a layout copy)."""

from benchmark.layer_metrics.local_ms import scoped_ms


def read(run):
    return scoped_ms(run, "dopt_conv1")
