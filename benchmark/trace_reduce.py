"""From a profiler trace (``*.xplane.pb``) to the numbers the per-layer
metrics read, with nothing but ``jax.profiler.ProfileData``.

The arithmetic is on plain intervals and is tested on hand-made cases and
on a small recorded trace (``tests/``):

* busy = length of the UNION of the device's op intervals inside the
  steady window (the host span ``bench.window`` that the harness opens
  after warm-up: never the compile);
* idle = window - busy; each idle gap is attributed to the innermost
  host span open at its middle;
* an op's self time = its duration minus what its nested ops cover (a
  ``while`` contains its body's ops on the same line);
* time under a scope = union of the ops whose name stack carries it;
* collective time = union of collective ops; its exposed part = what no
  other leaf op on that device overlaps.

This chip's raw planes carry no jax name stack: an op event's name is
the HLO instruction's text, its stats are times only.  The name stack
(and with it the program's ``dopt_update`` / ``dopt_mix`` scopes and the
convolutions) comes from the compiled round program's HLO text, joined on
the instruction name (``name_stacks_from_hlo``).  Collectives that run
asynchronously span their ``-start`` .. ``-done`` on the ``Async XLA Ops``
line, which is read for them alone.

A trace this module cannot read (no device plane, no op line, no window
span) raises: a broken reduction fails the run, it does not degrade.
"""

from __future__ import annotations

import dataclasses
import re
from pathlib import Path

WINDOW_SPAN = "bench.window"
OP_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"

COLLECTIVE_MARKERS = ("all-reduce", "all-gather", "reduce-scatter",
                      "collective-permute", "all-to-all", "allreduce",
                      "allgather", "reducescatter", "collectivepermute",
                      "alltoall")
# "conv" but not "convert" (dtype casts are everywhere on a bf16 path).
_CONV = re.compile(r"conv(?!ert)")
PHASES = ("conv", "comm", "update", "other")


@dataclasses.dataclass(frozen=True)
class Op:
    start: float      # ns
    end: float        # ns
    name: str         # HLO instruction name, e.g. "fusion.6"
    stack: str = ""   # jax name stack from the HLO metadata ("" if none)

    @property
    def text(self) -> str:
        return f"{self.name} {self.stack}".lower()


@dataclasses.dataclass
class Reduced:
    window: tuple[float, float]              # ns
    devices: dict[str, list[Op]]             # plane name -> ops in window
    host_spans: list[tuple[str, float, float]]
    # plane name -> asynchronous collectives (start .. done) in window
    async_collectives: dict[str, list[Op]] = dataclasses.field(
        default_factory=dict)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


# ---------------------------------------------------------------- intervals

def merge(intervals) -> list[tuple[float, float]]:
    """Sorted, disjoint union of (start, end) intervals."""
    out: list[list[float]] = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(merged) -> float:
    return sum(e - s for s, e in merged)


def subtract(merged_a, merged_b) -> list[tuple[float, float]]:
    """The parts of ``merged_a`` that ``merged_b`` does not cover."""
    out = []
    j = 0
    for s, e in merged_a:
        cur = s
        while j < len(merged_b) and merged_b[j][1] <= cur:
            j += 1
        k = j
        while k < len(merged_b) and merged_b[k][0] < e:
            bs, be = merged_b[k]
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def _nesting_order(ops: list[Op]) -> list[int]:
    """Indices by start, a container before what it contains."""
    return sorted(range(len(ops)),
                  key=lambda i: (ops[i].start, -(ops[i].end - ops[i].start)))


def self_times(ops: list[Op]) -> list[float]:
    """Each op's duration minus what ops nested inside it cover (ops of
    one line nest properly or not at all).  Same order as ``ops``."""
    order = _nesting_order(ops)
    selfs = [ops[i].end - ops[i].start for i in range(len(ops))]
    stack: list[int] = []
    for i in order:
        while stack and ops[stack[-1]].end <= ops[i].start:
            stack.pop()
        if stack:
            selfs[stack[-1]] -= min(ops[i].end, ops[stack[-1]].end) - ops[i].start
        stack.append(i)
    return [max(s, 0.0) for s in selfs]


def leaves(ops: list[Op]) -> list[Op]:
    """Ops with no op nested inside them (an op that merely overlaps
    another ran on a different line of the device and is no container)."""
    order = [ops[i] for i in _nesting_order(ops)]
    out = []
    for i, o in enumerate(order):
        j, nested = i + 1, False
        while j < len(order) and order[j].start < o.end:
            if order[j].end <= o.end:
                nested = True
                break
            j += 1
        if not nested:
            out.append(o)
    return out


# ------------------------------------------------------------- classification

def is_collective(op: Op) -> bool:
    """By the instruction's own name (XLA names an instruction after its
    opcode); an op that merely consumes a collective's result is not."""
    return any(m in op.name.lower() for m in COLLECTIVE_MARKERS)


def classify_phase(op: Op) -> str:
    """conv | comm | update | other, by the program's two named scopes
    and the op's own class (the rule of ``dopt.utils.profiling``, copied:
    the update tag wins, then collectives and the mixing scope, then
    convolutions)."""
    t = op.text
    if "dopt_update" in t:
        return "update"
    if is_collective(op) or "dopt_mix" in t:
        return "comm"
    if _CONV.search(t):
        return "conv"
    return "other"


# ------------------------------------------------------------------ reductions

def busy_ns(ops: list[Op]) -> float:
    return length(merge((o.start, o.end) for o in ops))


def idle_gaps(ops: list[Op], window) -> list[tuple[float, float]]:
    return subtract([window], merge((o.start, o.end) for o in ops))


def scope_ns(ops: list[Op], scope: str) -> float:
    return length(merge((o.start, o.end) for o in ops if scope in o.text))


def phase_ns(ops: list[Op]) -> dict[str, float]:
    out = {p: 0.0 for p in PHASES}
    for op, s in zip(ops, self_times(ops)):
        out[classify_phase(op)] += s
    return out


def collective_ns(ops: list[Op], async_ops=()) -> tuple[float, float]:
    """(total, exposed): the union of collective ops (with the
    asynchronous ones' start-to-done spans), and the part of it during
    which no other leaf op runs on the device."""
    lv = leaves(ops)
    coll = merge([(o.start, o.end) for o in lv if is_collective(o)]
                 + [(o.start, o.end) for o in async_ops])
    rest = merge((o.start, o.end) for o in lv if not is_collective(o))
    return length(coll), length(subtract(coll, rest))


def op_label(op: Op) -> str:
    """The name stack's tail past the jit wrappers, else the HLO op name
    with its numeric suffix dropped: stable across recompiles."""
    if op.stack:
        parts = [p for p in op.stack.split("/")
                 if p and not p.startswith(("jit(", "pjit"))]
        if parts:
            return "/".join(parts[-4:])
    return re.sub(r"[.\d]+$", "", op.name) or op.name


_HLO_NAME_STACK = re.compile(
    r'^\s*(?:ROOT\s+)?%?([^\s=]+)\s*=.*?metadata=\{[^}]*?op_name="([^"]*)"')


def name_stacks_from_hlo(hlo_text: str) -> dict[str, str]:
    """instruction name -> jax name stack, from a compiled program's HLO
    text (``op_name`` in each instruction's metadata)."""
    out = {}
    for line in hlo_text.splitlines():
        m = _HLO_NAME_STACK.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def instruction_name(event_name: str) -> str:
    """``"%fusion.6 = (f32[]...) fusion(...)"`` -> ``"fusion.6"``."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def top_ops(ops: list[Op], n: int = 10) -> list[list]:
    """The ``n`` labels with the most self time: [[label, seconds], ...]."""
    tot: dict[str, float] = {}
    for op, s in zip(ops, self_times(ops)):
        label = op_label(op)
        tot[label] = tot.get(label, 0.0) + s
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v * 1e-9] for k, v in ranked]


def gaps_by_host_span(ops: list[Op], window, host_spans,
                      n: int = 10) -> list[list]:
    """Idle seconds of the device by what the host was doing: each gap
    goes to the innermost (shortest) host span open at its middle."""
    tot: dict[str, float] = {}
    for s, e in idle_gaps(ops, window):
        mid = 0.5 * (s + e)
        open_ = [(he - hs, name) for name, hs, he in host_spans
                 if hs <= mid < he and name != WINDOW_SPAN]
        name = min(open_)[1] if open_ else "no_span"
        tot[name] = tot.get(name, 0.0) + (e - s)
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v * 1e-9] for k, v in ranked]


# --------------------------------------------------------------------- loading

def is_device_plane(name: str) -> bool:
    return name.startswith("/device:TPU:")


def reduce_profile(profile, span_names, name_stacks=None) -> Reduced:
    """``profile`` is a ``jax.profiler.ProfileData``; ``span_names`` the
    host spans to keep (the window span is always kept); ``name_stacks``
    maps instruction names to jax name stacks (``name_stacks_from_hlo``)."""
    keep = set(span_names) | {WINDOW_SPAN}
    stacks = name_stacks or {}
    host_spans: list[tuple[str, float, float]] = []
    device_ops: dict[str, list[Op]] = {}
    async_ops: dict[str, list[Op]] = {}
    for plane in profile.planes:
        if is_device_plane(plane.name):
            for line in plane.lines:
                if line.name not in (OP_LINE, ASYNC_LINE):
                    continue
                ops = [Op(ev.start_ns, ev.start_ns + ev.duration_ns, name,
                          stacks.get(name, ""))
                       for ev in line.events
                       for name in (instruction_name(ev.name),)]
                if line.name == OP_LINE:
                    device_ops.setdefault(plane.name, []).extend(ops)
                else:
                    async_ops.setdefault(plane.name, []).extend(
                        o for o in ops if is_collective(o))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in keep:
                        host_spans.append((ev.name, ev.start_ns,
                                           ev.start_ns + ev.duration_ns))
    if not any(device_ops.values()):
        raise ValueError(
            f"the trace has no {OP_LINE!r} line on a device plane "
            f"(planes: {[p.name for p in profile.planes]})")
    windows = [(s, e) for name, s, e in host_spans if name == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN!r} host span in the "
                         f"trace, found {len(windows)}")
    window = windows[0]

    def inside(o):
        return o.start >= window[0] and o.end <= window[1]

    kept_ops = {}
    for name, ops in device_ops.items():
        kept = [o for o in ops if inside(o)]
        total = sum(o.end - o.start for o in ops)
        if total and sum(o.end - o.start for o in kept) < 0.99 * total:
            raise ValueError(
                f"{name}: under 99% of the traced op time lies inside the "
                "window span; device and host clocks disagree or the "
                "window was not steady")
        kept_ops[name] = kept
    return Reduced(
        window=window, devices=kept_ops, host_spans=host_spans,
        async_collectives={name: [o for o in async_ops.get(name, [])
                                  if inside(o)] for name in kept_ops})


def reduce_file(path, span_names, name_stacks=None) -> Reduced:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(str(path)), span_names,
                          name_stacks)


def find_xplane(trace_dir) -> Path:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if len(found) != 1:
        raise ValueError(f"expected one .xplane.pb under {trace_dir}, "
                         f"found {len(found)}")
    return found[0]
