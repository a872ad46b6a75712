"""Bytes the compiled round program moves between chips: the result
buffers of every cross-device collective in the compiled HLO text, async
pairs counted once at their ``-start`` and every ``channel_id`` once (the
TPU compiler prints clones of one collective, all with its channel id: 125
all-gather lines for the 64 gathers of the ResNet fleet, PERF.md PR 22).
A count, not a time; 0 on one chip.  (The program has a richer twin, ``hlo_collective_bytes``; the
yardstick keeps its own so that no later PR can move it.)"""

from __future__ import annotations

import re

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter",
               "collective-permute", "all-to-all")
DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
               "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
               "f64": 8}
_SHAPE = re.compile(r"\b([a-z]+[0-9]+|pred)\[([0-9,]*)\]")


def _shape_bytes(text: str) -> int:
    total = 0
    for dtype, dims in _SHAPE.findall(text):
        if dtype not in DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * DTYPE_BYTES[dtype]
    return total


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """``{kind: bytes, ..., "total": bytes}`` over the HLO text."""
    per_channel: dict[tuple, int] = {}
    for n, line in enumerate(hlo_text.splitlines()):
        lhs, eq, rhs = line.partition("=")
        if not eq:
            continue
        rhs = rhs.strip()
        for kind in COLLECTIVES:
            m = re.search(rf"(^|\s){re.escape(kind)}(-start)?\(", rhs)
            if m:
                channel = re.search(r"channel_id=(\d+)", rhs)
                key = (kind, channel.group(1) if channel else f"line{n}")
                per_channel[key] = max(per_channel.get(key, 0),
                                       _shape_bytes(rhs[:m.start()]))
                break
    out = {k: 0 for k in COLLECTIVES}
    for (kind, _), nbytes in per_channel.items():
        out[kind] += nbytes
    out["total"] = sum(out[k] for k in COLLECTIVES)
    return out
