"""Device milliseconds a round of the decoder's attention that none of
its named parts covers: the RMS norm of the layer input and its cast,
the residual add after the output projection, what XLA leaves under
``dopt_attn`` alone, and the bubbles inside a scoped ``while`` (the
indexed attention's ``lax.map`` over a run of blocks covers its body:
the time between the ops of one step is in here).  The time that
``attn_ms`` reads (ops under ``dopt_attn`` and the ``splash_mqa_*``
kernels) less the union of its children: ``dopt_qkv``, ``dopt_rope``,
``dopt_oproj``, ``dopt_splash``, the splash kernels, ``dopt_index`` and
``dopt_attend`` with their kernels (``dopt_attn_dopt_*``), so that
``attn_ms`` = that union + this, exactly, on the chip where ``attn_ms``
is longest.  A recompute counts as its scope does.  Left out where no op
carries ``dopt_qkv`` (a program from before these scopes)."""

from benchmark import trace_reduce as tr
from benchmark.layer_metrics.local_ms import scoped_ms

PARENT = ("dopt_attn", "splash_mqa_")
CHILDREN = ("dopt_qkv", "dopt_rope", "dopt_oproj", "dopt_splash",
            "splash_mqa_", "dopt_index", "dopt_attend", "dopt_attn_dopt_")


def remainder_ms(run, parent, children):
    """ms a round that ops whose text holds one of ``parent`` cover and
    no op whose text holds one of ``children`` does (which is
    ``length(merge(parent | children)) - length(merge(children))``), on
    the chip where the parent's union is longest; ``None`` where there is
    nothing to read or no op carries ``dopt_qkv``."""
    if not scoped_ms(run, "dopt_qkv"):
        return None

    def union(ops, marks):
        return tr.merge((o.start, o.end) for o in ops
                        if any(m in o.text for m in marks))

    ops = max(run.reduced.devices.values(),
              key=lambda ops: tr.length(union(ops, parent)))
    ns = tr.length(tr.subtract(union(ops, parent), union(ops, children)))
    return ns * 1e-6 / run.rounds


def read(run):
    return remainder_ms(run, PARENT, CHILDREN)
