"""Device milliseconds a round spends in the decoder's attention, forward
and backward: RMS norm of the layer input, the q/k/v and gate
projections, rotary embedding, the causal (full or banded) attention,
the per-head output gate and the output projection.  Ops under the
program's ``dopt_attn`` scope (what XLA leaves under it: a fusion
carries the name stack of its root op only) together with the fused
attention kernels, which the compiler leaves without a name stack and
which go by their own names (``splash_mqa_fwd`` / ``_dq`` / ``_dkv``);
busiest chip."""

from benchmark import trace_reduce as tr
from benchmark.layer_metrics.local_ms import scoped_ms

SCOPE = "dopt_attn"
KERNELS = "splash_mqa_"


def read(run):
    if scoped_ms(run, SCOPE) is None:
        return None
    ns = max(tr.length(tr.merge(
        (o.start, o.end) for o in ops
        if SCOPE in o.text or KERNELS in o.text))
        for ops in run.reduced.devices.values())
    return ns * 1e-6 / run.rounds
