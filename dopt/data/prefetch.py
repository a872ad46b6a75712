"""Prefetched host staging: overlap batch planning with device compute.

A pre-round record (since deleted; not measured on today's code) put
the fast leg at 2.26 wall rounds/sec against 2.51 device rounds/sec —
~10% of every block being the host serially building batch plans and
``device_put``-ing them while the TPU idles.  The
blocked loops' host work is *prefetchable*: batch plans and the stacked
fault/link/corrupt inputs are (or split into parts that are) stateless
in ``(seed, round)``, so block b+1's payload can be built and staged to
device while block b runs.  The engines' loops become
dispatch → stage-next → fetch instead of build → dispatch → fetch.

The ordering contract that keeps prefetch-on runs BIT-IDENTICAL to
prefetch-off (History, fault ledger, canonical telemetry stream):

* **draw vs build.**  Each block's staging splits into a cheap,
  possibly-stateful *draw* (host RNG draws — the federated sampling
  stream, the gossip matching-matrix stream — plus the per-round fault
  vectors) and an expensive, *pure* build (``make_batch_plan`` over the
  drawn keys, ``np.stack``, ``jax.device_put``).  Draws always run on
  the caller's thread, in block order — exactly the sequence positions
  the unprefetched loop consumes them at — so stateful streams advance
  identically.  Only the pure build runs on the background thread.
* **replay never draws.**  The engines' post-fetch ledger/telemetry
  replay consumes the block's *drawn* inputs (``w_raw=...``,
  ``chosen=...``) rather than re-drawing, so staging block b+1 before
  block b's replay cannot perturb any stream.
* **no staging across a commit point.**  A checkpoint boundary is a
  commit: everything the checkpoint captures (RNG states, host
  mirrors, the registry) must reflect exactly the committed rounds.
  The loops therefore never stage past a scheduled checkpoint —
  equivalently, prefetched-but-uncommitted staging is discarded at
  every checkpoint/resume point — so a killed-and-resumed prefetch run
  replays bit-identically (the resumed loop simply re-stages from the
  checkpointed state).

The queue is bounded at depth 2: the block being consumed plus at most
one staged successor.  ``take()`` of an un-staged key falls back to an
inline build (the first block of every run, and the block after a
checkpoint), which is the unprefetched code path.
"""

from __future__ import annotations

import threading
import time


def next_block_rounds(ts: list, remaining: int, block: int,
                      next_ckpt: int | None) -> list:
    """The rounds of the block a prefetching loop stages while block
    ``ts`` runs: none past the run's end (``remaining`` rounds after
    ``ts``), and staging never crosses a scheduled checkpoint boundary
    (the block after a checkpoint builds inline from committed state)."""
    end_round = ts[-1] + 1
    if remaining > 0 and (next_ckpt is None or end_round < next_ckpt):
        return [end_round + j for j in range(min(block, remaining))]
    return []


def timed_build(build, timers):
    """Wrap a pure block ``build`` so its runtime accumulates into
    ``timers``' ``host_batch_plan`` totals from the stager's background
    thread (the ``PhaseTimers`` tracer spans are not meant for
    concurrent cross-thread use, so the wrapper accounts the span
    directly — the engines' inline path uses the same key, never
    concurrently with a staged build of the same block)."""

    def wrapped(meta):
        t0 = time.perf_counter()  # dopt: allow-wallclock -- span timing only, never training math
        out = build(meta)
        timers.add("host_batch_plan", time.perf_counter() - t0)  # dopt: allow-wallclock -- span timing only, never training math
        return out

    return wrapped


class _Staged:
    """One in-flight background build (a bare thread per block: builds
    are long relative to thread spawn, and a pool would outlive the
    trainer)."""

    __slots__ = ("_out", "_err", "_thread")

    def __init__(self, build, meta):
        self._out = None
        self._err: BaseException | None = None
        self._thread = threading.Thread(
            target=self._run, args=(build, meta),
            name="dopt-prefetch", daemon=True)
        self._thread.start()

    def _run(self, build, meta) -> None:
        try:
            self._out = build(meta)
        except BaseException as e:  # surfaced at take()
            self._err = e

    def wait(self):
        self._thread.join()
        if self._err is not None:
            raise self._err
        out, self._out = self._out, None
        return out

    def wait_quiet(self) -> None:
        """Join and drop the result (discard path) — a failed discarded
        build is not an error, its payload was never going to be used."""
        self._thread.join()
        self._out = self._err = None


class PrefetchStager:
    """Bounded background staging queue for the blocked run loops.

    ``stage(key, build, meta)`` starts ``build(meta)`` on a background
    thread; ``take(key)`` joins and returns its payload, or ``None``
    when nothing was staged under that key (caller builds inline).
    ``build`` MUST be pure — every stateful draw belongs in the
    caller-side code that produced ``meta`` (see module docstring).
    """

    def __init__(self, *, depth: int = 2):
        if depth < 2:
            raise ValueError(f"PrefetchStager depth={depth} must be >= 2 "
                             "(the consumed block plus one staged)")
        self.depth = int(depth)
        self._pending: dict = {}

    def __len__(self) -> int:
        return len(self._pending)

    def stage(self, key, build, meta) -> None:
        """Begin building ``key``'s payload in the background."""
        if key in self._pending:
            raise RuntimeError(f"block {key!r} is already staged")
        if len(self._pending) >= self.depth - 1:
            raise RuntimeError(
                f"staging queue full ({len(self._pending)} pending, "
                f"depth {self.depth}): take() the oldest block first")
        self._pending[key] = _Staged(build, meta)

    def take(self, key):
        """The staged payload for ``key`` (blocking on its build), or
        ``None`` when it was never staged.  Any *other* pending keys
        are discarded — a key miss means the run's cursor moved (e.g.
        a resume), and stale payloads must not leak into later takes."""
        staged = self._pending.pop(key, None)
        if self._pending:
            self.discard()
        if staged is None:
            return None
        return staged.wait()

    def discard(self) -> None:
        """Drop every pending payload (checkpoint/resume points, loop
        teardown).  Joins the background builds first so no thread
        outlives the state it captured."""
        pending, self._pending = self._pending, {}
        for staged in pending.values():
            staged.wait_quiet()
