"""Device milliseconds a round spends in the body of a learned sparse
attention once the keys are chosen, forward and backward: the scores of
a block of queries against every key the block sees, the mask, the
float32 softmax, the value product and the head-mean of the
probabilities that the alignment term takes.  Ops under the program's
``dopt_attend`` scope (inside ``dopt_attn``), busiest chip.  Left out
where the program has no such scope."""

from benchmark.layer_metrics.local_ms import scoped_ms


def read(run):
    return scoped_ms(run, "dopt_attend") or None
