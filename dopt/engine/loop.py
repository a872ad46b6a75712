"""The host round loop, once: plan → dispatch → stage-next → wait →
commit → fetch → record, for a block of ``k ≥ 1`` rounds.

Both engines run every execution path (gossip per-round and blocked;
federated per-round, blocked, chaos and population) through
``HostLoop._run_loop``.  A path is a ``RoundPath``: the five callables
that differ, plus how many rounds one jitted call fuses and whether the
next block's build may run ahead on the stager's thread.  Every host
span of the round (tree in ``dopt.utils.profiling``) and the
``dopt_round`` step annotation open here and nowhere else under
``dopt/engine/`` (``seqlm.py``'s step loop aside).

The contracts the loop enforces, for every path:

* **draw vs build** (``dopt.data.prefetch``): ``draw`` runs on this
  thread, in block order, at exactly the sequence positions the
  unprefetched loop consumes the stateful streams at; only the pure
  ``build`` may run on the stager's thread.
* **staging never crosses a scheduled checkpoint**: the block after a
  checkpoint builds inline from committed state, so a checkpoint
  captures exactly the committed rounds and a killed-and-resumed
  prefetch run replays bit-identically.
* **carried state is read at dispatch time**: ``launch`` runs after the
  previous block's ``commit`` and ``record`` — only the plan payload is
  ever staged ahead.
* **one fetch a block**: the packed metrics vector is the only
  device→host transfer; everything ``record`` derives (ledger rows,
  host mirrors, telemetry) is post-fetch replay, which is what makes
  the streams identical across paths.
"""

from __future__ import annotations

import dataclasses
import math
import time
from typing import Any, Callable

import jax
import numpy as np

from dopt.data.prefetch import (PrefetchStager, next_block_rounds,
                                timed_build)


def _identity(meta):
    return meta


@dataclasses.dataclass(frozen=True)
class RoundPath:
    """What one execution path hands the loop.

    ``draw(ts)`` is the STATEFUL half of block ``ts``'s staging (host
    RNG draws, fault vectors, ledger rows); ``build(meta)`` the PURE
    half (batch plans, ``device_put``).  ``launch(payload)`` returns
    ``(fn_name, fn, args, kwargs)`` with the carried state read now;
    ``commit(out)`` assigns the engine's carried state from the jitted
    call's result and returns the packed metrics; ``record(payload,
    packed)`` replays the block's rounds on the host (rows, ledgers,
    telemetry) and advances ``engine.round`` by the block's length.

    ``prefetch`` may only be set where ``build`` is pure and ``draw``
    reads no state a pending ``record`` still has to write: the
    per-round paths, whose one builder (``_round_dispatch``) reads the
    carried state, leave it off."""

    draw: Callable[[list], Any]
    launch: Callable[[Any], tuple]
    commit: Callable[[Any], Any]
    record: Callable[[Any, np.ndarray], None]
    build: Callable[[Any], Any] = _identity
    block: int = 1
    prefetch: bool = False


class HostLoop:
    """What the two engines share of the host side of a run: the round
    loop, its end-of-run and per-block telemetry, the checkpoint
    wrapper and the serve-mode driver.  The engine supplies ``timers``,
    ``round``, ``history``, ``telemetry``, ``_save(path)`` and its
    ``RoundPath``s.  Only with telemetry attached does the loop read
    more of it: ``engine_kind``, ``num_workers``, the quarantine
    mirrors, ``_registry``, ``_diag`` / ``_diag_keys`` and
    ``_consensus_operands()``."""

    # Serve-mode hook (dopt.serve): ``run_served`` drives the loop one
    # round per controller tick and defers the end-of-run summary gauge
    # to the drain boundary.
    _suppress_run_summary = False

    def _run_loop(self, path: RoundPath, rounds: int,
                  checkpoint_every: int = 0, checkpoint_path=None):
        """Run ``rounds`` rounds in blocks of up to ``path.block``.
        Periodic auto-checkpoints land at block boundaries (the state
        only exists on the host there)."""
        t0 = time.time()  # dopt: allow-wallclock -- total_time wall meter, reporting only
        next_ckpt = ((self.round // checkpoint_every + 1) * checkpoint_every
                     if checkpoint_every else None)
        stager = PrefetchStager() if path.prefetch else None
        try:
            done = 0
            while done < rounds:
                k = min(path.block, rounds - done)
                ts = [self.round + j for j in range(k)]
                next_ts = (next_block_rounds(ts, rounds - (done + k),
                                             path.block, next_ckpt)
                           if stager is not None else [])
                with self.timers.step(ts[0]):
                    self._run_block(path, ts, next_ts, stager)
                    done += k
                    if next_ckpt is not None and self.round >= next_ckpt:
                        self.save(checkpoint_path)
                        next_ckpt = ((self.round // checkpoint_every + 1)
                                     * checkpoint_every)
        finally:
            if stager is not None:
                stager.discard()
        self.total_time = time.time() - t0  # dopt: allow-wallclock -- total_time wall meter, reporting only
        self._run_summary_telemetry()
        return self.history

    def _run_block(self, path: RoundPath, ts: list, next_ts: list,
                   stager) -> None:
        """One block: plan (unless staged), dispatch, stage ``next_ts``
        while the device runs, wait, commit, fetch, record."""
        timers = self.timers
        payload = stager.take(ts[0]) if stager is not None else None
        if payload is None:
            with timers.phase("host_batch_plan"):
                payload = path.build(path.draw(ts))
        fn_name, fn, args, kwargs = path.launch(payload)
        with timers.phase("round_step"):
            # The jit dispatch returns before the device finishes: the
            # next block's staging overlaps this block's device time,
            # and block_until_ready is the barrier before the fetch.
            with timers.phase("round_dispatch"):
                out = fn(*args, **kwargs)
            if next_ts:
                with timers.phase("host_batch_plan"):
                    meta = path.draw(next_ts)
                stager.stage(next_ts[0], timed_build(path.build, timers),
                             meta)
            with timers.phase("round_wait"):
                jax.block_until_ready(out)
        packed = path.commit(out)
        with timers.phase("round_fetch"):
            packed = np.asarray(packed)  # ONE device→host fetch per block
        with timers.phase("round_record"):
            path.record(payload, packed)
            self._device_telemetry(ts[-1], fn_name, fn)

    def run_served(self, controller) -> str:
        """Resident serve-mode entry (``dopt.serve``): train one round
        at a time until the round-boundary ``controller`` says
        otherwise — the "run until told otherwise" loop a daemon owns
        instead of a ``--rounds N`` script.

        ``controller.boundary(trainer)`` is called BEFORE each round
        with the trainer at a consistent round boundary; it may apply
        control-plane effects (membership directives, checkpoints,
        ledgered ``control`` rows) and returns ``"run"`` to train one
        more round or a stop verdict: ``"drain"`` (graceful stop —
        the one end-of-run summary gauge is emitted here, matching a
        scripted ``run()``'s cadence), ``"restart"`` (checkpoint and
        hand control back for a process re-exec; NO summary gauge —
        the resumed daemon's drain emits it, so an interrupted and an
        uninterrupted serve emit identical streams), or ``"rebuild"``
        (the daemon must reconstruct the trainer from an updated
        config, restore, and call ``run_served`` again)."""
        self._suppress_run_summary = True
        try:
            while True:
                verdict = controller.boundary(self)
                if verdict != "run":
                    if verdict == "drain":
                        self._suppress_run_summary = False
                        self._run_summary_telemetry()
                    return verdict
                self.run(rounds=1)
        finally:
            self._suppress_run_summary = False

    def save(self, path) -> None:
        """Checkpoint the full training state (``_save``: the engine's
        arrays, round, history, and host RNG state — a resumed run must
        continue its stateful draws, not replay round 0's)."""
        with self.timers.phase("checkpoint"):
            self._save(path)
        if self.telemetry is not None:
            # Cadence telemetry for the monitor's checkpoint-cadence
            # rule (dopt.obs.rules) — emitted AFTER the atomic save
            # landed, so the stream never claims a checkpoint a kill
            # could have torn.  The consensus snapshot rides the
            # checkpoint event (params are being fetched for
            # serialization anyway), NOT a gauge: checkpoint timing is
            # call-pattern state, and gauges must stay identical across
            # execution paths (ConsensusStallRule(use_checkpoints=True)
            # opts in).
            ev = {"round": int(self.round)}
            cd = self._consensus_value()
            if cd is not None:
                ev["consensus_distance"] = cd
            self.telemetry.emit("checkpoint", **ev)  # dopt: allow-nondet-event -- checkpoint cadence is an execution-path property, documented non-deterministic

    # -- telemetry (dopt.obs) ------------------------------------------
    def _round_telemetry(self, t: int, frows: list, diag=None) -> None:
        """Emit round t's telemetry bundle: the fault-ledger rows as
        typed events, the history row just appended as the ``round``
        event, and the host-mirror state (quarantine streaks, the
        engine's ``_mirror_gauges``, the population registry) plus the
        fetched on-device diagnostics block (``diagnostics="on"``) as
        ``gauge`` events.  Everything here derives from the same
        post-fetch host-replay data, at the identical point of every
        path's ``record``, so the streams are bit-identical across
        execution paths; ``telemetry=None`` skips it entirely."""
        tele = self.telemetry
        if tele is None:
            return
        quarantined = int((self._quarantine_until > t).sum())
        gauges = {
            "quarantine_active": float(quarantined),
            "screen_streak_max": float(self._screen_streak.max()),
            # Denominator gauge for the monitor's fleet-fraction rules
            # (dopt.obs.rules): lanes eligible to contribute this round.
            "participating_lanes": float(self.num_workers - quarantined),
        }
        if diag is not None:
            from dopt.obs.events import finite_diag_gauges

            gauges.update(finite_diag_gauges(self._diag_keys, diag))
        gauges.update(self._mirror_gauges())
        if self._registry is not None:
            reg = self._registry
            gauges["cohort_size"] = float(reg.cohort_size)
            # Denominator for the monitor's client-keyed quarantine
            # storm (population_quarantined / population_size).
            gauges["population_size"] = float(reg.clients)
            gauges["population_quarantined"] = float(
                (reg.quarantine_until > t).sum())
            gauges["population_sampled_total"] = float(
                (reg.participation > 0).sum())
        tele.emit_round_bundle(t, engine=self.engine_kind,
                               metrics=self.history.rows[-1],
                               faults=frows, gauges=gauges)

    def _mirror_gauges(self) -> dict:
        """Host-mirror gauges only this engine has."""
        return {}

    def _device_telemetry(self, t: int, fn_name: str, fn) -> None:
        """Non-deterministic resource/compile channel — shared impl in
        ``dopt.utils.profiling.emit_device_resource``."""
        from dopt.utils.profiling import emit_device_resource

        emit_device_resource(self, t, fn_name, fn)

    def _consensus_value(self) -> float | None:
        """Mean over workers of ‖xᵢ − c‖₂ over the engine's
        ``_consensus_operands()`` (the stacked tree and, optionally, its
        centre), or None when there is nothing to report (round 0, an
        engine without per-worker state, or a diverged fleet)."""
        if self.round == 0:
            return None
        if jax.process_count() > 1:
            # Multi-process fleet: the reduction below is a COLLECTIVE
            # over cross-process-sharded params, but only the telemetry
            #-attached leader reaches this call site — computing it
            # would strand the leader in a collective the followers
            # never join.  Fleets report consensus via diagnostics="on"
            # (inside the compiled round, all processes) instead.
            return None
        operands = self._consensus_operands()
        if operands is None:
            return None
        from dopt.obs import consensus_distance

        cd = consensus_distance(*operands)
        return cd if math.isfinite(cd) else None

    def _run_summary_telemetry(self) -> None:
        """End-of-``run()`` consensus-distance gauge — one fetch per
        run() call; identical across execution paths for an identical
        call pattern.  Suppressed under ``diagnostics="on"``: the diag
        block already carries a TRUE per-round dispersion meter in
        every round bundle (watermark-suppressed on resume), and the
        end-of-run gauge is per-``run()``-CALL state — a killed-and-
        resumed run would emit an extra one mid-stream, breaking the
        gauges-included canonical equality diagnostics guarantees."""
        tele = self.telemetry
        if tele is None or self._diag or self._suppress_run_summary:
            return
        cd = self._consensus_value()
        if cd is not None:
            tele.emit("gauge", round=self.round - 1,
                      name="consensus_distance", value=cd,
                      engine=self.engine_kind)
