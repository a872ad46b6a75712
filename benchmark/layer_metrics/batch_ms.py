"""Device milliseconds a round spends gathering rows from the resident
train arrays (a step's batch, a chunk's slab, a holdout): ops under the
program's ``dopt_batch`` scope, busiest chip.  It reads what XLA leaves
under the scope: a gather fused into its consumer is the consumer's."""

from benchmark.layer_metrics.local_ms import scoped_ms


def read(run):
    return scoped_ms(run, "dopt_batch")
