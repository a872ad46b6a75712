"""Device milliseconds a round spends recomputing forward values for the
backward pass: ops under ``rematted_computation``, jax's own name for
the recompute of a ``jax.checkpoint`` (the decoder's layers, attention
blocks and head blocks), busiest chip.  What a checkpoint keeps is not
recomputed, so this falls when a layer keeps more.  It reads what XLA
leaves under the name: a fusion carries the name stack of its root op
only.  0.0 on a program with no checkpoint."""

from benchmark.layer_metrics.local_ms import scoped_ms


def read(run):
    return scoped_ms(run, "rematted_computation")
