"""Run ONE cell of ``BENCHMARK.json`` once, in a new process:

    python3 -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Load and warm up (that is ``setup_s``), measure for ``--seconds``
(``--trace 0``) or trace a few calls (``--trace 1``), read the peak
memory, give the chip back, check parity with the plain reference, print.  The LAST stdout line is one JSON object
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` and, when
traced, ``breakdown``); everything else goes to stderr and to
``benchmark/out/``.  Without a TPU, or with fewer chips than the cell
asks for, it exits non-zero and prints no result.

``--rehearse`` is for the CPU sandbox only: a toy of the cell (four
virtual devices for a four-chip cell), the whole control flow, and no
metric under a device name.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file found by its name in ``BENCHMARK.json`` (README.md).
"""

from __future__ import annotations

import time

T_START = time.perf_counter()   # before the heavy imports: they are set-up

import argparse
import dataclasses
import gc
import importlib
import json
import math
import os
import shutil
import statistics
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
HERE = Path(__file__).resolve().parent
OUT = HERE / "out"
# Host spans kept for ``breakdown.idle_gaps``: the harness's own call span
# and the program's (it writes each to the profiler itself).  A gap goes to
# the innermost of them open at its middle; no metric reads it.
HOST_SPANS = ("bench.run_call", "host_batch_plan", "round_step",
              "round_dispatch", "round_wait", "round_fetch", "round_record")


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def load_cell(name: str) -> dict:
    """The cell's entry, files and metric lists, all found by name."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; "
                         f"one of {sorted(cells)}")
    cell = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]

    def listed(metric):
        return "workloads" not in metric or name in metric["workloads"]

    return {
        "name": name, "chips": cell["chips"],
        "config": json.loads((ROOT / entry["file"]).read_text()),
        "traffic": json.loads(
            (HERE / "traffic" / f"{cell['traffic']}.json").read_text()),
        "end_to_end": [m for m in bench["end_to_end"] if listed(m)],
        "per_layer": [m for m in bench["per_layer"] if listed(m)],
    }


def percentile_line(samples_ms: list[float]) -> str:
    """Median and the highest percentile with ten samples beyond it."""
    n = len(samples_ms)
    s = sorted(samples_ms)
    line = f"n={n} p50={statistics.median(s):.3f}ms"
    if n >= 20:
        p = 1.0 - 10.0 / n
        line += f" p{100 * p:.1f}={s[n - 11]:.3f}ms"
    else:
        line += " (under 20 samples: no percentile has ten beyond it)"
    return line + f" min={s[0]:.3f}ms max={s[-1]:.3f}ms"


@dataclasses.dataclass
class Window:
    """What set-up and the window leave behind, once the trainer is gone."""

    setup_s: float
    warmup_s: list
    call_s: list                 # wall seconds of each call in the window
    rounds_per_call: int
    samples_per_round: int
    losses: dict                 # round -> the trainer's training loss
    host_span_s: dict            # the program's timers, window only
    compile_s: float             # before the window: compile or cache load
    programs: int
    cache_hits: int
    compiles_in_window: int
    peak_bytes: list             # per used chip: see ``chip_peaks``
    round_hlo: str               # the compiled round program's HLO text
    round_memory: dict           # ... and what the compiler reserves for it
    trace_dir: Path

    @property
    def rounds(self) -> int:
        return len(self.call_s) * self.rounds_per_call

    @property
    def round_ms(self) -> list:
        return [1e3 * c / self.rounds_per_call for c in self.call_s]


def parse_args(argv):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    return ap.parse_args(argv)


def set_up_and_measure(args, cell, cfg, meter) -> Window:
    """Build the trainer, warm up, run the window (traced or timed), read
    the peak, and let go of the trainer."""
    import jax

    from benchmark import adapter, flops, trace_reduce

    config, traffic = cell["config"], cell["traffic"]
    trainer = adapter.build_trainer(cfg, traffic, config)
    have = adapter.param_count(trainer)
    listed = flops.param_count(config["layers"])
    # A rehearsal that overrides the model holds a smaller one than listed.
    toy = args.rehearse and "model" in config.get("rehearsal", {})
    if not (config["parameters"] == listed and (toy or have == listed)):
        raise SystemExit(
            f"the trainer holds {have} parameters a worker, the "
            f"configuration file says {config['parameters']} and its layer "
            f"list {listed}")
    per_call = traffic["rounds_per_call"]

    def run_call() -> float:
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.run_call"):
            trainer.run(rounds=per_call)
            jax.block_until_ready(trainer.params)
        return time.perf_counter() - t0

    warm = [run_call() for _ in range(traffic["warmup_calls"])]
    programs, compile_s = meter.snapshot()
    hits = meter.cache_hits
    spans_before = dict(trainer.timers.totals)
    setup_s = time.perf_counter() - T_START
    log(f"setup {setup_s:.2f}s: compile/load {compile_s:.2f}s over "
        f"{programs} programs, {hits} cache hits; warm-up calls "
        f"{[round(w, 3) for w in warm]}s")

    calls: list[float] = []
    trace_dir = OUT / "trace" / cell["name"]
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    t_win = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
            if args.trace or args.rehearse:
                for _ in range(traffic["trace_calls"]):
                    calls.append(run_call())
            else:
                while time.perf_counter() - t_win < args.seconds:
                    calls.append(run_call())
    finally:
        if args.trace:
            jax.profiler.stop_trace()
    compiles_in_window = meter.compiles - programs
    round_hlo, round_memory = adapter.compiled_round(trainer)
    peak_bytes = chip_peaks(jax.devices()[:cell["chips"]], round_memory)
    return Window(
        setup_s=setup_s, warmup_s=warm, call_s=calls,
        rounds_per_call=per_call,
        samples_per_round=adapter.samples_per_round(trainer, cfg, traffic),
        losses=adapter.losses(trainer, traffic),
        host_span_s={k: v - spans_before.get(k, 0.0)
                     for k, v in trainer.timers.totals.items()},
        compile_s=compile_s, programs=programs, cache_hits=hits,
        compiles_in_window=compiles_in_window, peak_bytes=peak_bytes,
        round_hlo=round_hlo, round_memory=round_memory, trace_dir=trace_dir)


def chip_peaks(devices, round_memory: dict) -> list:
    """Peak bytes on each chip the cell uses, read right after the window
    (before the parity job can raise it, with the trainer still alive).

    PJRT's ``peak_bytes_in_use`` on this chip counts live buffers (data,
    fleet state, results) and leaves out what a running program reserves
    for its temporaries (PERF.md, PR 22: four chips running 32 ResNets
    peaked at a chip's share of state plus data).  The chip must hold
    both, so the peak is the larger of the runtime's counter and the live
    bytes between rounds plus the round program's temporaries and
    un-aliased outputs as the compiler reports them."""
    program = round_memory["temp"] + max(
        round_memory["output"] - round_memory["alias"], 0)
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(max(stats.get("peak_bytes_in_use", 0),
                         stats.get("bytes_in_use", 0) + program
                         if stats else 0))
    return peaks


TRIM = 0.2   # share of the window's calls set aside at EACH end


def steady_call_s(call_s: list) -> float:
    """Mean seconds of a call over the window once the slowest and the
    fastest fifth of the calls (by count, rounded down) are set aside.

    The plain mean turns ONE stalled call into the whole run's number: a
    1.1 s stall in one of eleven 1.7 s calls cost a run 5.4% of its rate,
    and the driver's check saw such runs often enough to spread a set of
    six by 3.9% (PERF.md, PR 22).  Two stalls in eleven calls now move
    nothing; a change that slows more than a fifth of the rounds still
    shows in full.  Under five calls nothing is set aside."""
    s = sorted(call_s)
    k = int(TRIM * len(s))
    return statistics.fmean(s[k:len(s) - k])


def end_to_end(win: Window, loss_round) -> dict:
    values = {
        "train_samples_per_s": win.samples_per_round * win.rounds_per_call
            / steady_call_s(win.call_s),
        "round_ms_p50": statistics.median(win.round_ms),
        "setup_s": win.setup_s,
    }
    if math.isfinite(win.losses.get(loss_round, math.nan)):
        values["loss_at_round"] = win.losses[loss_round]
    return values


def per_layer(args, cell, win: Window, device: dict):
    """The cell's per-layer metrics from the traced window, and the
    breakdown; adds ``busy_s`` / ``window_s`` to ``device``."""
    from benchmark import trace_reduce
    from benchmark.context import LayerInput

    reduced, breakdown = None, None
    if not args.rehearse:            # the CPU has no device plane to read
        reduced = trace_reduce.reduce_file(
            trace_reduce.find_xplane(win.trace_dir), HOST_SPANS,
            trace_reduce.name_stacks_from_hlo(win.round_hlo))
        # The chip whose ops the HLO names best: on four chips the profiler
        # labels every op of chip 0's plane "region.N" (PERF.md, PR 22).
        first = max(reduced.devices.values(),
                    key=lambda ops: sum(1 for o in ops if o.stack))
        breakdown = {
            "device_ops": trace_reduce.top_ops(first),
            "idle_gaps": trace_reduce.gaps_by_host_span(
                first, reduced.window, reduced.host_spans),
        }
        log("phase split (self time, best-named chip, ms a round): "
            + str({p: round(v * 1e-6 / win.rounds, 3)
                   for p, v in trace_reduce.phase_ns(first).items()}))
        device["busy_s"] = 1e-9 * statistics.fmean(
            trace_reduce.busy_ns(ops) for ops in reduced.devices.values())
        device["window_s"] = reduced.window_s
    run = LayerInput(
        reduced=reduced, rounds=win.rounds, chips=cell["chips"],
        device_kind=device["kind"], config=cell["config"],
        traffic=cell["traffic"], samples_per_round=win.samples_per_round,
        host_span_s=win.host_span_s, compile_s=win.compile_s,
        round_hlo=win.round_hlo, peak_bytes=win.peak_bytes)
    (OUT / f"{cell['name']}.round.hlo.txt").write_text(win.round_hlo)
    values = {}
    for m in cell["per_layer"]:
        reader = importlib.import_module(
            f"benchmark.layer_metrics.{m['name']}")
        value = reader.read(run)
        if value is not None:
            values[m["name"]] = value
    return values, breakdown


def main(argv=None) -> int:
    args = parse_args(argv)
    cell = load_cell(args.workload)
    chips, traffic, config = cell["chips"], cell["traffic"], cell["config"]
    if args.rehearse:
        if os.environ.get("JAX_PLATFORMS") != "cpu":
            raise SystemExit("--rehearse is for JAX_PLATFORMS=cpu only")
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={chips}")
    import jax

    from benchmark import adapter, flops, parity
    from benchmark.meter import CompileMeter, device_info

    device = device_info()
    log(f"device: {device}")
    if not args.rehearse and device["platform"] != "tpu":
        log(f"no TPU: jax found platform {device['platform']!r}")
        return 1
    if device["count"] < chips:
        log(f"the cell needs {chips} chips, jax found {device['count']}")
        return 1
    if not args.rehearse:
        flops.device_peaks(device["kind"])   # unknown device: error now
    log(f"compile cache: {adapter.enable_compile_cache()}")
    # Cache the ~150 sub-second programs too (eager set-up ops, the
    # reference's steps): by default JAX persists only what took over a
    # second to compile, and every run would compile the rest again.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    OUT.mkdir(exist_ok=True)

    cfg = adapter.build_config(cell["name"], config, traffic,
                               seed=args.seed, chips=chips)
    if args.rehearse:
        cfg = adapter.rehearsal_config(cfg, traffic, config.get("rehearsal"))
    with CompileMeter() as meter:
        win = set_up_and_measure(args, cell, cfg, meter)
    # The parity job runs last, on a chip the cell has given back: it is
    # neither set-up nor window, and cannot touch the cell's peak.
    gc.collect()
    check = parity.run(cfg, config, traffic)
    log(f"parity: error {check['error']:.3e} (reference moved "
        f"{check['moved']:.3e}), held as {check['of']!r} to "
        f"{check['tolerance']:.1e} because: {check['why']}; "
        f"{check['seconds']:.1f}s")

    # ------------------------------------------------------ correctness
    first = traffic["warmup_calls"] * win.rounds_per_call
    bad = [t for t in range(first, first + win.rounds)
           if not math.isfinite(win.losses.get(t, math.nan))]
    k = traffic["loss_round"]
    # Only an untraced run is long enough to be held to reaching round K.
    wants_k = not args.trace and any(m["name"] == "loss_at_round"
                                     for m in cell["end_to_end"])
    missed_k = wants_k and not math.isfinite(win.losses.get(k, math.nan))
    attempted = win.rounds + (1 if wants_k else 0)
    failed = len(bad) + (1 if missed_k else 0)
    correct = (not bad and not missed_k and win.compiles_in_window == 0
               and check["ok"])
    log(f"window: {len(win.call_s)} calls x {win.rounds_per_call} rounds in "
        f"{sum(win.call_s):.3f}s; round time {percentile_line(win.round_ms)}; "
        f"compilations in the window {win.compiles_in_window}; loss at "
        f"K={k}: {win.losses.get(k)}; losses "
        f"{[round(v, 4) for _, v in sorted(win.losses.items())][:40]}")
    raw = win.samples_per_round * win.rounds / sum(win.call_s)
    slow = sum(c > 1.05 * statistics.median(win.call_s) for c in win.call_s)
    log(f"whole window, every call counted: {raw:.1f} samples/s; "
        f"{slow} of {len(win.call_s)} calls over 1.05 x the median")
    log(f"peak bytes per chip {win.peak_bytes}; the round program reserves "
        f"{win.round_memory}")

    # ---------------------------------------------------------- metrics
    breakdown = None
    if args.trace:
        values, breakdown = per_layer(args, cell, win, device)
        listed = cell["per_layer"]
        log("the round time above is a TRACED one: compare it with an "
            "untraced run for the tracing overhead")
    else:
        values = end_to_end(win, k)
        listed = cell["end_to_end"]
    units = {m["name"]: m["unit"] for m in listed}
    metrics = {n: {"value": float(v), "unit": units[n]}
               for n, v in values.items() if n in units}
    device["memory_peak_bytes"] = int(max(win.peak_bytes))
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    # Every number ``correct`` compared, beside its limit: last in the
    # result line and last on stderr.
    compared = {
        f"parity_error_{check['of']}": {"value": check["compared"],
                                        "limit": check["tolerance"]},
        "compiles_in_window": {"value": win.compiles_in_window, "limit": 0},
        "rounds_without_finite_loss": {"value": len(bad), "limit": 0},
    }
    if wants_k:
        compared["loss_round_missed"] = {"value": int(missed_k), "limit": 0}
    if args.rehearse:
        # A CPU run gives counts, never a number under a device metric.
        result = {"rehearsal": True, "correct": result["correct"],
                  "attempted": attempted, "failed": failed, "metrics": {},
                  "device": device, "would_report": sorted(metrics),
                  "rounds": win.rounds,
                  "samples_per_round": win.samples_per_round}
    result["compared"] = compared
    detail = {**result, "workload": cell["name"], "seed": args.seed,
              "trace": args.trace, "parity": check, "round_ms": win.round_ms,
              "whole_window_samples_per_s": raw, "slow_calls": slow,
              "warmup_s": win.warmup_s,
              "losses": {str(t): v for t, v in sorted(win.losses.items())},
              "host_span_s": win.host_span_s, "compile_s": win.compile_s,
              "programs": win.programs, "cache_hits": win.cache_hits,
              "compiles_in_window": win.compiles_in_window,
              "round_memory": win.round_memory}
    (OUT / f"{cell['name']}.seed{args.seed}.trace{args.trace}.json"
     ).write_text(json.dumps(detail, indent=1))
    for name, c in compared.items():
        log(f"compared: {name} {c['value']:.6g} limit {c['limit']:.6g}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
