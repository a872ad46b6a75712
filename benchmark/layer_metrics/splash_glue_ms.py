"""Device milliseconds a round that the fused causal attention costs
around its kernels, forward and backward: what XLA puts around the
kernels' calls to lay out their operands and outputs, and what the
kernels' backward computes outside them (the row sums of the output
times its cotangent).  The queries' scale and cast in front of the call
are ``rope_ms``'s.  Ops under the program's ``dopt_splash`` scope less the kernels
themselves (``splash_mqa_*``, found by their own names and subtracted
even where one carries the scope's name stack), busiest chip.  Left out
where the program has no such scope, or the attention takes the
``jax.numpy`` blocks."""

from benchmark import trace_reduce as tr
from benchmark.layer_metrics.local_ms import scoped_ms

SCOPE = "dopt_splash"
KERNELS = "splash_mqa_"


def read(run):
    if not scoped_ms(run, SCOPE):
        return None
    ns = max(tr.length(tr.subtract(
        tr.merge((o.start, o.end) for o in ops
                 if SCOPE in o.text and KERNELS not in o.text),
        tr.merge((o.start, o.end) for o in ops if KERNELS in o.text)))
        for ops in run.reduced.devices.values())
    return ns * 1e-6 / run.rounds
