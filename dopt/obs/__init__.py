"""dopt.obs — unified telemetry: event stream, span tracing, sinks.

The signals the ROADMAP's service mode needs (fault ledger, phase
fractions, live run metrics) used to be scattered across
``History.rows``, the ledger, bench-only JSON lines and one-off
scripts.  This package is the one substrate:

* a structured **JSONL event stream** with a versioned schema
  (``dopt.obs.events``): per-round ``round`` events, host-mirror
  ``gauge`` events, the fault ledger re-emitted as typed ``fault``
  events, plus ``phase``/``bench``/``warning`` producer events;
* host-side **span tracing** (``dopt.obs.spans``) with a Chrome-trace
  export, hooked into the engines' existing ``PhaseTimers`` sites;
* a **sink layer** (``dopt.obs.sinks``): JSONL file, in-memory ring,
  Prometheus text snapshot;
* a **streaming health monitor** (``dopt.obs.monitor`` +
  ``dopt.obs.rules``): a declarative rule set evaluated over the live
  stream (in-process sink or JSONL tail), emitting ``alert`` events
  and an end-of-run ``HealthReport`` verdict — with a scrape endpoint
  (``python -m dopt.obs.serve``: /metrics + /healthz), a live terminal
  tail (``python -m dopt.obs.watch``), and a bench perf-regression
  ledger (``dopt.obs.regress`` over ``results/bench_history.jsonl``).

Hard invariants:

* **Off path** — ``trainer.telemetry`` defaults to None and every
  emission site is python-gated on it, entirely on the HOST side of
  the post-fetch boundary: with telemetry off the engines run the
  exact pre-change host loop and compile the exact pre-change device
  programs (pinned by tests/test_obs.py's bit-identity test).
* **Execution-path equality** — events of the deterministic kinds
  (``round``/``fault``/``gauge``) are derived only from the same
  host-replay data the ledger already uses, at the same post-fetch
  points, so per-round and blocked execution emit bit-identical
  streams (``canonical()`` is the comparison form).
* **Resume watermark** — ``Telemetry.to_jsonl(path, resume=True)``
  recovers the highest streamed round from the file and suppresses
  re-emission below it, so a killed-and-resumed run continues the
  stream with a gapless, duplicate-free round sequence
  (``python -m dopt.obs.check`` enforces it).

Emission cadence note: the per-round ``round``/``fault``/``gauge``
bundle replays identically on every path; ``consensus_distance`` is
computed from the final device state once per ``run()`` call (one
fetch, identical across paths for an identical call pattern), and
``phase`` events come from profiler-traced windows (bench.py).
"""

from __future__ import annotations

import functools
from typing import Any, Iterable, Mapping

from dopt.obs.events import (DETERMINISTIC_KINDS, KINDS, SCHEMA_VERSION,
                             canonical, check_stream, make_event,
                             sanitize_metrics, validate_event)
from dopt.obs.latency import (SLO_LATENCIES, LatencyHistogram,
                              summarize_latency_events)
from dopt.obs.monitor import HealthMonitor, HealthReport, JsonlTail
from dopt.obs.rules import RULES, build_rules, default_rules
from dopt.obs.sinks import JsonlSink, MemorySink, PrometheusSink, Sink
from dopt.obs.spans import SpanTracer

__all__ = [
    "DETERMINISTIC_KINDS", "KINDS", "RULES", "SCHEMA_VERSION",
    "SLO_LATENCIES", "FleetAggregator", "FleetMetricsServer",
    "HealthMonitor", "HealthReport", "JsonlSink", "JsonlTail",
    "LatencyHistogram", "MemorySink", "PrometheusSink", "Sink",
    "SpanTracer", "Telemetry", "attach", "build_rules", "canonical",
    "check_stream", "consensus_distance", "default_rules",
    "first_divergence", "make_event", "sanitize_metrics",
    "summarize_latency_events", "validate_event",
]


def __getattr__(name: str):
    # The fleet aggregation layer and the stream differ are imported
    # lazily: they are CLI-facing modules with their own http.server /
    # argparse surface, and the hot telemetry path (engines importing
    # dopt.obs per round bundle) should not pay for them.
    if name in ("FleetAggregator", "FleetMetricsServer"):
        from dopt.obs import aggregate

        return getattr(aggregate, name)
    if name == "first_divergence":
        from dopt.obs.diff import first_divergence

        return first_divergence
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


class Telemetry:
    """Emitter facade: builds schema-stamped events, fans them out to
    the sinks, owns the span tracer and the monotonic round watermark."""

    def __init__(self, sinks: Iterable[Sink] = (), *, watermark: int = 0):
        self.sinks: list[Sink] = list(sinks)
        self.tracer = SpanTracer()
        self.watermark = int(watermark)

    @classmethod
    def to_jsonl(cls, path, *, resume: bool = False,
                 ring: int = 0) -> "Telemetry":
        """JSONL-file telemetry.  ``resume=True`` appends and recovers
        the round watermark from the existing file (kill-and-resume
        continues the stream instead of duplicating rounds); ``ring``
        > 0 additionally keeps the last N events in memory
        (``.sinks[-1].events``)."""
        wm = 0
        if resume:
            prev = JsonlSink.scan_watermark(path)
            wm = 0 if prev is None else prev + 1
        sinks: list[Sink] = [JsonlSink(path, append=resume)]
        if ring:
            sinks.append(MemorySink(capacity=ring))
        return cls(sinks, watermark=wm)

    # -- emission ------------------------------------------------------
    def emit(self, kind: str, **fields: Any) -> dict[str, Any]:
        ev = make_event(kind, **fields)
        for s in self.sinks:
            s.emit(ev)
        return ev

    def emit_round_bundle(self, t: int, *, engine: str,
                          metrics: Mapping[str, Any],
                          faults: Iterable[Mapping[str, Any]] = (),
                          gauges: Mapping[str, float] | None = None) -> bool:
        """One round's deterministic events, in the canonical order:
        the fault-ledger rows (typed), the host-mirror gauges, then the
        ``round`` event LAST — it is the bundle's commit record: a
        kill-torn bundle has no round event, so ``repair_tail`` drops
        the orphans and the resumed run re-emits the bundle whole
        (round-first would seal a bundle whose gauges never landed).
        Suppressed wholesale (returns False) below the resume
        watermark; advances the watermark past ``t``."""
        t = int(t)
        if t < self.watermark:
            return False
        bundle = [make_event("fault", round=int(r["round"]),
                             worker=int(r["worker"]), fault=str(r["kind"]),
                             action=str(r["action"])) for r in faults]
        bundle.extend(make_event("gauge", round=t, name=name,
                                 value=float(value), engine=engine)
                      for name, value in (gauges or {}).items())
        bundle.append(make_event("round", round=t, engine=engine,
                                 metrics=sanitize_metrics(metrics)))
        # One batched dispatch per round: the JSONL sink turns the
        # bundle into a single flushed write, so a kill never tears a
        # round's fault events apart from its round event (the resume
        # watermark would re-emit them as duplicates otherwise).
        for s in self.sinks:
            s.emit_many(bundle)
        self.watermark = t + 1
        return True

    # -- spans ---------------------------------------------------------
    def span(self, name: str):
        return self.tracer.span(name)

    def write_trace(self, path):
        return self.tracer.write_chrome(path)

    def close(self) -> None:
        for s in self.sinks:
            s.close()


def attach(trainer, telemetry: Telemetry, *, fresh: bool = False,
           checkpoint_every: int | None = None) -> Telemetry:
    """Wire a Telemetry into a trainer: sets ``trainer.telemetry``
    (read by the engines' python-gated emission sites), hooks the span
    tracer into the trainer's ``PhaseTimers`` (every existing
    ``phase``/``measure`` site becomes a span), and emits the stream
    segment header.  ``fresh=True`` resets the round watermark to 0 —
    for a NEW logical run sharing a sink with earlier ones (bench's
    legs); resumed runs keep the watermark ``to_jsonl(resume=True)``
    recovered.  ``checkpoint_every`` stamps the run's configured
    checkpoint cadence (rounds) on the header so the monitor's
    checkpoint_cadence rule knows what to expect without being told
    out of band."""
    if fresh:
        telemetry.watermark = 0
    trainer.telemetry = telemetry
    trainer.timers.tracer = telemetry.tracer
    engine = getattr(trainer, "engine_kind", type(trainer).__name__.lower())
    # The segment starts wherever the trainer will actually emit from:
    # a checkpoint-resumed trainer streaming into a FRESH file starts
    # at trainer.round, not 0 — a header claiming 0 would make the
    # checker reject the (valid) stream at the first round event.
    start = max(telemetry.watermark, int(getattr(trainer, "round", 0) or 0))
    telemetry.watermark = start
    telemetry.emit("run", engine=engine,
                   name=getattr(getattr(trainer, "cfg", None), "name", None)
                   or "run",
                   round=start,
                   workers=getattr(trainer, "num_workers", None),
                   checkpoint_every=(int(checkpoint_every)
                                     if checkpoint_every else None))
    return telemetry


def consensus_distance(stacked, center=None) -> float:
    """Mean over workers of ‖xᵢ − c‖₂ for a worker-stacked pytree —
    the fleet-disagreement meter.  ``center`` defaults to the stacked
    mean (gossip); the federated engines pass theta.  ONE compiled
    reduction + one scalar fetch (an eager reduction is dozens of
    separately launched multi-device ops over a sharded fleet);
    deterministic for bit-identical inputs, so every execution path of
    the same run reports the same value."""
    import jax

    centers = None if center is None else jax.tree.leaves(center)
    return float(_consensus_reduction()(jax.tree.leaves(stacked), centers))


@functools.cache
def _consensus_reduction():
    """The jitted body of ``consensus_distance``, built on first use
    (importing ``dopt.obs`` must not import jax): per leaf the float32
    sum of squares of each worker's distance from the centre, summed
    over the leaves in tree order, then sqrt and the mean over
    workers."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def reduction(leaves, centers):
        if centers is None:
            centers = [leaf.astype(jnp.float32).mean(axis=0)
                       for leaf in leaves]
        sq = None
        for p, c in zip(leaves, centers):
            d = (p.astype(jnp.float32)
                 - c.astype(jnp.float32)[None]).reshape(p.shape[0], -1)
            s = (d * d).sum(axis=1)
            sq = s if sq is None else sq + s
        return jnp.sqrt(sq).mean()

    return reduction
