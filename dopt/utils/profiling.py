"""Tracing / profiling (absent in the reference — SURVEY §5).

The reference's only instrumentation is ``time.time()`` around
``run()`` printed as "Total Run Time" plus tqdm bars (servers.py:51,79;
simulators.py:115-137).  dopt has ONE system, always on:

* ``PhaseTimers`` — named wall-clock accumulators for the round's host
  phases.  Every span is also a ``jax.profiler.TraceAnnotation``, so a
  profiler capture (``python -m dopt.run --trace DIR``, the benchmark's
  traced run) holds the host spans and the device ops in one file on
  one clock; ``--timers`` prints the totals of any run.
* ``jax.named_scope`` at the source of each device layer; the scope
  lands in the ``op_name`` metadata of the compiled round program.
* ``trace()`` — context manager around ``jax.profiler``.

Host span tree of a round (a block in the blocked loops)::

    dopt_round                    StepTraceAnnotation, step_num = round
      host_batch_plan             plan, W_t / client sample, upload
      round_step                  the jitted call until its result is ready
        round_dispatch            ... until the call returns (transfer, launch)
        round_wait                block_until_ready
      round_fetch                 the one np.asarray(packed) a round
      round_record                unpack, screen feedback, history, telemetry
      checkpoint                  when one is due

Device scopes: ``dopt_local`` (the local phase), ``dopt_batch`` (the
on-device gather of a step's rows), ``dopt_update`` (momentum SGD),
``dopt_eval`` (every evaluation inside a round program; the holdout's
per-epoch eval is nested in ``dopt_local``), ``dopt_mix`` (consensus or
aggregation), ``dopt_pool`` (the differentiated 2×2 max-pool's forward
and backward, nested in ``dopt_local``; ``dopt.models.zoo``),
``dopt_conv1`` (the reference CNNs' first convolution over the stacked
fleet with its bias add, forward and backward, nested in ``dopt_local``
and in ``dopt_eval``; ``dopt.models.zoo``), and the decoder's, all
nested in ``dopt_local`` (``dopt.models.decoder``):
``dopt_attn`` (normed input to gated output projection) and, in a layer
with an indexer, inside it ``dopt_index`` (indexer projections, index
scores, selection, alignment term) with ``dopt_select`` inside that
(the k-th largest score and the mask) and ``dopt_attend`` (masked
scores, softmax, values, the head-mean), ``dopt_moe``
(router to combined output; the held experts' grouped-matmul kernels go
by their own names, ``dopt_moe_experts_fwd`` / ``_dx`` / ``_dw``, which
spell the scope out) with ``dopt_route`` inside it (scores, top-k,
combine weights, and the dispatch around the kernels: the slots' layout
and the combine weights' gradient, not the expert matmuls),
``dopt_head`` (final norm, logits, loss).

The rule: no span or scope without a reader — each of these is read by
a per-layer metric of ``BENCHMARK.json`` (table in PERF.md §3).  A PR
that adds or renames a scope bumps
``dopt.utils.compile_cache.PROGRAM_METADATA_VERSION``: the persistent
cache's key ignores metadata, so an executable cached before the change
would come back without the scope.

Note on async dispatch: jax returns before device work finishes, so a
``phase()`` context around a jit call measures dispatch only.  Use
``measure(name, fn, *args)`` to attribute device time — it blocks on
the function's result via ``block_until_ready``.
"""

from __future__ import annotations

import contextlib
import re
import time
from collections import defaultdict
from typing import Any, Iterator

import jax

ROUND_STEP = "dopt_round"


class PhaseTimers:
    """Accumulates wall-clock per named phase: total, count and the
    longest single duration (one stalled round in seventy is invisible
    in a mean and plain in a max).

    Every span is written to the profiler as well (a ``TraceAnnotation``
    costs well under a microsecond while no trace runs).  ``tracer`` is
    the telemetry hook (``dopt.obs.SpanTracer`` — or anything with a
    ``span(name)`` context manager): when set, every ``phase``/``measure``
    additionally records a nested host span there, so attaching
    telemetry to a trainer instruments all its existing timer sites
    with zero run-loop changes."""

    def __init__(self, tracer=None) -> None:
        self.totals: dict[str, float] = defaultdict(float)
        self.counts: dict[str, int] = defaultdict(int)
        self.maxima: dict[str, float] = defaultdict(float)
        self.tracer = tracer

    def add(self, name: str, seconds: float) -> None:
        """Account one finished span of ``name``."""
        self.totals[name] += seconds
        self.counts[name] += 1
        if seconds > self.maxima[name]:
            self.maxima[name] = seconds

    @contextlib.contextmanager
    def phase(self, name: str) -> Iterator[None]:
        """Host wall-clock for the block (dispatch-only for jit calls —
        use ``measure`` to include device time)."""
        span = (self.tracer.span(name) if self.tracer is not None
                else contextlib.nullcontext())
        t0 = time.perf_counter()  # dopt: allow-wallclock -- phase span timing, not training math
        try:
            with jax.profiler.TraceAnnotation(name), span:
                yield
        finally:
            self.add(name, time.perf_counter() - t0)  # dopt: allow-wallclock -- phase span timing, not training math

    def measure(self, name: str, fn, *args, **kwargs):
        """Run fn, block on its result, attribute the time to ``name``
        and, inside it, to ``round_dispatch`` (until fn returns) and
        ``round_wait`` (until its result is ready)."""
        with self.phase(name):
            with self.phase("round_dispatch"):
                out = fn(*args, **kwargs)
            with self.phase("round_wait"):
                jax.block_until_ready(out)
        return out

    def step(self, t: int):
        """The profiler's step annotation for round (or block start)
        ``t``: xprof's step view, and which round a device op ran in."""
        return jax.profiler.StepTraceAnnotation(ROUND_STEP, step_num=int(t))

    def summary(self) -> dict[str, dict[str, float]]:
        return {
            name: {
                "total_s": round(self.totals[name], 4),
                "count": self.counts[name],
                "mean_s": round(self.totals[name] / max(self.counts[name], 1), 5),
                "max_s": round(self.maxima[name], 5),
            }
            for name in self.totals
        }

    def report(self) -> str:
        rows = ["phase                total_s   count   mean_s     max_s"]
        for name, s in sorted(self.summary().items(),
                              key=lambda kv: -kv[1]["total_s"]):
            rows.append(f"{name:20s} {s['total_s']:8.3f} {s['count']:7d} "
                        f"{s['mean_s']:9.5f} {s['max_s']:9.5f}")
        return "\n".join(rows)


@contextlib.contextmanager
def trace(log_dir: str) -> Iterator[None]:
    """XLA profiler trace (TensorBoard/Perfetto-viewable)."""
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


# ---------------------------------------------------------------------
# Round-phase attribution: conv / mixing-comm / update / other
# ---------------------------------------------------------------------
# The round's device time decomposes into the conv stack (the actual
# training math), the consensus/aggregation phase (collectives + the
# mixing contraction, tagged ``dopt_mix`` at the source), and the
# optimizer/weight-update phase (tagged ``dopt_update``).  bench.py
# surfaces these fractions in its JSON line so "conv fraction >= X%"
# claims are measured from the trace, not guessed.

_COMM_MARKERS = ("all-reduce", "all-gather", "reduce-scatter",
                 "collective-permute", "all-to-all", "allreduce",
                 "allgather", "reducescatter", "collectivepermute",
                 "alltoall")

# "conv" but NOT "convert": dtype-conversion ops are everywhere on the
# bf16 fast leg and must not inflate the conv fraction (the acceptance
# metric) with cast overhead.
_CONV_RE = re.compile(r"conv(?!ert)")

PHASES = ("conv", "comm", "update", "other")


def classify_phase(op_type: str | None, operation: str | None = None) -> str:
    """Classify one profiled op into conv | comm | update | other.

    ``op_type`` is the framework-op-stats category, ``operation`` the
    op's name (which carries the jax name stack, so the engines'
    ``dopt_update``/``dopt_mix`` named scopes land here).  Precedence:
    the update tag wins (a sharded update nests inside the mix scope),
    then cross-device collectives and anything in the mixing scope
    (the consensus contraction is comm-phase work even when it lowers
    to a local gemm), then convolutions."""
    t = (op_type or "").lower()
    n = (operation or "").lower()
    if "dopt_update" in n:
        return "update"
    if any(k in t for k in _COMM_MARKERS) or any(k in n for k in _COMM_MARKERS):
        return "comm"
    if "dopt_mix" in n:
        return "comm"
    if _CONV_RE.search(t) or _CONV_RE.search(n):
        return "conv"
    return "other"


def phase_totals(rows) -> dict[str, Any]:
    """Reduce ``(op_type, operation, self_time_us)`` rows to per-phase
    totals + fractions: ``{conv_us, ..., conv_fraction, ...}``.  Pure
    (no profiler dependency) so the classification is unit-testable."""
    tot = {k: 0.0 for k in PHASES}
    for op_type, operation, self_us in rows:
        tot[classify_phase(op_type, operation)] += float(self_us)
    dev = sum(tot.values())
    out: dict[str, Any] = {f"{k}_us": round(v, 1) for k, v in tot.items()}
    for k, v in tot.items():
        out[f"{k}_fraction"] = round(v / dev, 4) if dev > 0 else 0.0
    return out


def xplane_op_stats(trace_dir: str) -> dict[str, Any]:
    """Reduce a captured xplane to op-level self times (the shared
    reduction behind ``scripts/trace_roofline.py`` and ``bench.py``'s
    device-basis rounds/sec).

    Returns ``{device_self_time_us, host_self_time_us,
    device_categories: [{op_type, self_time_us, pct_of_device}],
    device_phases: {conv_us, comm_us, update_us, other_us,
    *_fraction}, top_device_ops: [...]}``.
    """
    import glob
    import json

    from xprof.convert import raw_to_tool_data

    paths = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if not paths:
        raise FileNotFoundError(f"no xplane.pb under {trace_dir}")
    data, _ = raw_to_tool_data.xspace_to_tool_data(paths,
                                                   "framework_op_stats", {})
    table = json.loads(data if isinstance(data, str) else data.decode())
    if isinstance(table, list):
        table = table[0]
    cols = [c["id"] for c in table["cols"]]
    idx = {c: i for i, c in enumerate(cols)}

    def val(row, col):
        cell = row["c"][idx[col]]
        return None if cell is None else cell.get("v")

    by_cat: dict[str, float] = {}
    device_total = host_total = 0.0
    ops = []
    phase_rows = []
    for row in table.get("rows", []):
        side = val(row, "host_or_device")
        self_us = float(val(row, "total_self_time") or 0.0)
        cat = val(row, "type") or "?"
        if side == "Device":
            device_total += self_us
            by_cat[cat] = by_cat.get(cat, 0.0) + self_us
            phase_rows.append((cat, val(row, "operation"), self_us))
            ops.append({
                "op_type": cat,
                "operation": val(row, "operation"),
                "occurrences": val(row, "occurrences"),
                "total_self_time_us": round(self_us, 1),
            })
        else:
            host_total += self_us
    ops.sort(key=lambda o: -o["total_self_time_us"])
    cat_rows = sorted(by_cat.items(), key=lambda kv: -kv[1])
    return {
        "device_self_time_us": round(device_total, 1),
        "host_self_time_us": round(host_total, 1),
        "device_categories": [
            {"op_type": k, "self_time_us": round(v, 1),
             "pct_of_device": round(100.0 * v / max(device_total, 1e-9), 2)}
            for k, v in cat_rows
        ],
        "device_phases": phase_totals(phase_rows),
        "top_device_ops": ops[:20],
    }


def device_stats_of(fn, *, trace_prefix: str = "dopt-devtime-",
                    telemetry=None) -> dict:
    """Run ``fn()`` under a profiler trace and return the full
    ``xplane_op_stats`` reduction (device self time + the
    conv/comm/update phase split).

    Degrades instead of raising mid-bench: if the profiler cannot
    start/stop or the xplane/tensorboard reduction fails (missing
    xprof stack, parse error), the returned dict carries NaN device
    time, empty breakdowns and a ``warning`` field describing the
    failure — and a ``warning`` telemetry event when ``telemetry``
    (``dopt.obs.Telemetry``) is supplied.  ``fn()``'s own exceptions
    still propagate (a failing workload is a real error).  The temp
    trace directory is removed on every path."""
    import shutil
    import tempfile

    td = tempfile.mkdtemp(prefix=trace_prefix)
    warning = None
    try:
        started = True
        try:
            jax.profiler.start_trace(td)
        except Exception as e:
            started = False
            warning = f"profiler start failed: {e!r}"
        try:
            fn()
        finally:
            if started:
                try:
                    jax.profiler.stop_trace()
                except Exception as e:
                    warning = warning or f"profiler stop failed: {e!r}"
        stats = None
        if warning is None:
            try:
                stats = xplane_op_stats(td)
            except Exception as e:
                warning = f"xplane reduction failed: {e!r}"
        if stats is None:
            stats = {"device_self_time_us": float("nan"),
                     "host_self_time_us": float("nan"),
                     "device_categories": [], "device_phases": {},
                     "top_device_ops": []}
        if warning is not None:
            stats["warning"] = warning
            if telemetry is not None:
                telemetry.emit("warning", message=warning,  # dopt: allow-nondet-event -- degraded-profiler warning, outside DETERMINISTIC_KINDS by design
                               source="device_stats_of")
        return stats
    finally:
        shutil.rmtree(td, ignore_errors=True)


def device_memory_stats(device=None) -> dict | None:
    """Device-memory occupancy snapshot: ``{live_bytes, peak_bytes,
    source}``.

    Uses the backend allocator's stats where the runtime exposes them
    (TPU/GPU ``Device.memory_stats``: ``bytes_in_use`` /
    ``peak_bytes_in_use`` — ``source="device"``); on backends without
    them (CPU jax returns None) falls back to the PROCESS resident set
    (live = current RSS from ``/proc/self/statm``, peak =
    ``ru_maxrss`` — ``source="host_rss"``), so callers always get a
    finite occupancy signal to report/alert on.  Returns None only when
    even the host fallback is unavailable.  This is the shared helper
    behind ``scripts/bench_seqlm.py``'s peak-HBM column, bench.py's
    ``hbm_peak_gb`` field and the engines' ``resource`` telemetry
    events (``diagnostics="on"``)."""
    if device is None:
        devs = jax.local_devices()
        device = devs[0] if devs else None
    stats = None
    if device is not None:
        stats = getattr(device, "memory_stats", lambda: None)()
    if stats and stats.get("peak_bytes_in_use") is not None:
        return {"live_bytes": int(stats.get("bytes_in_use", 0)),
                "peak_bytes": int(stats["peak_bytes_in_use"]),
                "source": "device"}
    try:
        import os
        import resource

        # Linux ru_maxrss is KiB (macOS reports bytes; this repo's
        # runtime surface is Linux — documented, not branched).
        peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
        try:
            with open("/proc/self/statm") as f:
                live = int(f.read().split()[1]) * os.sysconf("SC_PAGESIZE")
        except (OSError, ValueError, IndexError):
            live = peak
        return {"live_bytes": int(live), "peak_bytes": int(peak),
                "source": "host_rss"}
    except Exception:  # pragma: no cover - non-POSIX fallback
        return None


def emit_device_resource(trainer, t: int, fn_name: str, fn) -> None:
    """The NON-deterministic device-resource channel, shared by both
    engines (``diagnostics="on"`` + telemetry attached): an HBM/RSS
    occupancy sample per block at the post-fetch boundary
    (``resource``) and a ``compile`` event whenever the dispatched
    round function (re)traced since the last block.  Both kinds stay
    outside ``DETERMINISTIC_KINDS`` — sampling cadence is an
    execution-path property, like ``alert``/``checkpoint`` — so a
    diagnosed stream still compares canonically equal across paths.

    Reads/advances the trainer's ``_last_step_total`` watermark over
    its ``round_step`` phase-timer total (the dispatch wall that
    absorbed any compile — an upper bound on compile seconds) and its
    ``_compile_watch`` trace-cache watermark."""
    tele = trainer.telemetry
    if tele is None or not trainer._diag:
        return
    step_total = trainer.timers.totals.get("round_step", 0.0)
    seconds = max(step_total - trainer._last_step_total, 0.0)
    trainer._last_step_total = step_total
    comp = trainer._compile_watch.observe(fn_name, fn)
    if comp is not None:
        tele.emit("compile", round=int(t), fn=fn_name,  # dopt: allow-nondet-event -- retrace channel is execution-path state, documented non-deterministic
                  count=comp["count"], total=comp["total"],
                  seconds=round(seconds, 6))
    stats = device_memory_stats()
    if stats is not None:
        tele.emit("resource", round=int(t), engine=trainer.engine_kind,  # dopt: allow-nondet-event -- HBM occupancy sampling cadence is execution-path state, documented non-deterministic
                  **stats)


class CompileWatcher:
    """Retrace detector for jitted round functions.

    ``observe(name, fn)`` snapshots ``fn``'s trace-cache size and
    returns ``{"count": new_entries, "total": size}`` when the cache
    GREW since the previous observation of ``name`` — i.e. the last
    dispatch (re)traced — else None.  A healthy blocked run compiles
    each round function once at warmup; a compile event on every
    observation is the retrace storm the ``retrace_storm`` health rule
    (dopt.obs.rules) alerts on.  Tolerant of jit wrappers without
    ``_cache_size`` (returns None — no signal rather than a crash)."""

    def __init__(self) -> None:
        self._seen: dict[str, int] = {}

    def observe(self, name: str, fn) -> dict | None:
        size = getattr(fn, "_cache_size", None)
        if size is None:
            return None
        try:
            n = int(size())
        except Exception:
            return None
        prev = self._seen.get(name, 0)
        self._seen[name] = n
        if n > prev:
            return {"count": n - prev, "total": n}
        return None


def device_time_of(fn, *, trace_prefix: str = "dopt-devtime-",
                   telemetry=None) -> float:
    """Run ``fn()`` under a profiler trace and return the device self
    time in microseconds — the basis for rounds/sec that host noise
    cannot reach.
    NaN (plus a warning event, see ``device_stats_of``) when the
    profiler stack degrades."""
    return device_stats_of(fn, trace_prefix=trace_prefix,
                           telemetry=telemetry)["device_self_time_us"]


# ---------------------------------------------------------------------
# FLOP accounting (MFU meters for the benchmark harnesses)
# ---------------------------------------------------------------------

# Public per-chip peak throughput (bf16 matmul peak).  MFU for f32 runs
# is reported against the same bf16 peak so modes stay comparable — the
# hardware ceiling is the MXU's.
PEAK_FLOPS = {
    "TPU v5 lite": 197e12,   # v5e, bf16
    "TPU v5": 459e12,        # v5p, bf16
    "TPU v4": 275e12,
}


def device_peak_flops() -> tuple[str, float | None]:
    """(device_kind, bf16 peak FLOP/s or None when unknown, e.g. CPU)."""
    kind = jax.devices()[0].device_kind
    for k, v in PEAK_FLOPS.items():
        if kind.startswith(k):
            return kind, v
    return kind, None


def fwd_flops_per_sample(fn, params, input_shape, *, batch: int = 8,
                         dtype=None) -> float:
    """Forward-pass FLOPs per sample from XLA's compiled cost analysis.

    ``fn(params, x)`` is the forward callable (e.g. ``lambda p, x:
    model.apply({'params': p}, x)``).  Generic across the zoo — no
    per-model analytic tables — and counts what XLA actually lowers
    (convs at 2·MACs, elementwise, norms), so it is the right numerator
    for MFU accounting.  Uses a small batch and divides, which washes
    out fixed per-call ops."""
    import jax.numpy as jnp

    x = jnp.zeros((batch, *input_shape), dtype or jnp.float32)
    compiled = jax.jit(fn).lower(params, x).compile()
    ca = compiled.cost_analysis()
    if not ca or "flops" not in ca:
        # Some backends/jax versions return None or omit the key; NaN
        # lets callers (bench_suite) keep their throughput numbers and
        # skip the MFU fields instead of aborting the whole suite.
        return float("nan")
    return float(ca["flops"]) / batch


def train_flops_per_sample(fn, params, input_shape, *, batch: int = 8,
                           dtype=None) -> float:
    """Training FLOPs per sample ≈ 3 × forward (fwd + ~2× in backward)
    — the standard accounting used by the MFU literature."""
    return 3.0 * fwd_flops_per_sample(fn, params, input_shape, batch=batch,
                                      dtype=dtype)
