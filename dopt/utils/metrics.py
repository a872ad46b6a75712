"""Structured metrics sink (the reference's ``history`` pattern, typed).

Every reference orchestrator appends per-round dicts to ``history``
(``servers.py:77``, ``simulators.py:99-108``) and the notebooks dump
them to CSV (``results/*.csv``, columns
``round, avg_test_acc, avg_test_loss, avg_train_loss``).  ``History``
is one sink with both schemas: P1 federated
(round, test_acc, test_loss, train_loss, train_acc) and P2 gossip
(round, avg_test_acc, avg_test_loss, avg_train_loss); CSV export is
byte-compatible with the committed result files' column layout.
"""

from __future__ import annotations

import csv
import io
import json
import os
from pathlib import Path
from typing import Any, Iterator


def atomic_write_text(path: str | Path, text: str,
                      newline: str | None = None) -> Path:
    """Crash-safe file write: materialise into a same-directory temp
    file, then ``os.replace`` into place (atomic on POSIX).  A process
    killed mid-write leaves either the previous complete file or
    nothing — never a truncated artifact — matching the size-manifest
    hardening of ``dopt.utils.checkpoint``.  All History exports
    (results CSV/JSON, the ``--faults-json`` ledger) go through here.
    ``newline`` passes through to the write (the csv module's content
    carries its own ``\\r\\n`` terminators — pass ``""`` to keep them
    byte-exact instead of letting text mode re-translate)."""
    path = Path(path)
    path.parent.mkdir(parents=True, exist_ok=True)
    tmp = path.with_name(f".{path.name}.tmp{os.getpid()}")
    try:
        tmp.write_text(text, newline=newline)
        os.replace(tmp, path)
    finally:
        tmp.unlink(missing_ok=True)
    return path


class History:
    """Append-only per-round record store with CSV/JSON export."""

    def __init__(self, name: str = "history"):
        self.name = name
        self.rows: list[dict[str, Any]] = []
        # Fault ledger (dopt.faults): one row per injected fault —
        # {round, worker, kind, action} — so faulted runs are auditable
        # and replayable.  Appended by the engines as faults are
        # injected, checkpointed alongside ``rows``.
        self.faults: list[dict[str, Any]] = []

    def append(self, **row: Any) -> None:
        self.rows.append({k: _scalar(v) for k, v in row.items()})

    def log_fault(self, *, round: int, worker: int, kind: str,
                  action: str) -> None:
        """Record one injected fault in the ledger (schema: round,
        worker, kind ∈ dopt.faults.KINDS, action taken)."""
        self.faults.append({"round": int(round), "worker": int(worker),
                            "kind": str(kind), "action": str(action)})

    def faults_to_json(self, path: str | Path) -> Path:
        return atomic_write_text(path, json.dumps(self.faults, indent=2))

    @staticmethod
    def faults_from_json(path: str | Path) -> list[dict[str, Any]]:
        """Re-load a ``--faults-json`` export.  Round-trips the in-
        ``History`` ledger row-for-row (the schema is plain
        int/str scalars), so exported traces stay audit-complete —
        pinned by tests/test_network.py's round-trip test."""
        with open(path) as f:
            rows = json.load(f)
        if not isinstance(rows, list) or any(
                not isinstance(r, dict) for r in rows):
            raise ValueError(f"{path}: not a fault-ledger export "
                             "(expected a JSON list of row objects)")
        return rows

    def __len__(self) -> int:
        return len(self.rows)

    def __iter__(self) -> Iterator[dict[str, Any]]:
        return iter(self.rows)

    def __getitem__(self, key: str) -> list[Any]:
        """Column access: history['avg_test_acc'] -> list over rounds."""
        return [r.get(key) for r in self.rows]

    def last(self) -> dict[str, Any]:
        return self.rows[-1] if self.rows else {}

    # Reference results/*.csv column orders (P2 ``history`` dumps:
    # round, avg_test_acc, avg_test_loss, avg_train_loss; P1 ``history``:
    # round, test_acc, test_loss, train_loss, train_acc) — shared columns
    # are emitted in this order so dopt CSVs diff cleanly against the
    # reference's committed files; extra columns follow in first-seen
    # order.
    _CSV_ORDER = ("round", "avg_test_acc", "avg_test_loss",
                  "avg_train_loss", "test_acc", "test_loss", "train_loss",
                  "train_acc")

    def to_csv(self, path: str | Path) -> Path:
        """Write rows in the reference results/*.csv layout (leading
        unnamed index column, then the columns — union over ALL rows,
        since non-eval rounds carry fewer keys than eval rounds).
        Written atomically (``atomic_write_text``)."""
        seen: dict[str, None] = {}
        for r in self.rows:
            for k in r:
                seen.setdefault(k)
        cols = [c for c in self._CSV_ORDER if c in seen]
        cols += [c for c in seen if c not in cols]
        buf = io.StringIO()
        w = csv.writer(buf)
        w.writerow([""] + cols)
        for i, r in enumerate(self.rows):
            w.writerow([i] + [r.get(c, "") for c in cols])
        return atomic_write_text(path, buf.getvalue(), newline="")

    def to_json(self, path: str | Path) -> Path:
        return atomic_write_text(path, json.dumps(self.rows, indent=2))

    @classmethod
    def from_csv(cls, path: str | Path, name: str = "history") -> "History":
        h = cls(name)
        with open(path, newline="") as f:
            reader = csv.DictReader(f)
            for row in reader:
                # Blank cells are ABSENT keys, not empty strings: the
                # CSV layout unions heterogeneous row schemas (non-eval
                # rounds carry fewer keys than eval rounds) and fills
                # the gaps with "", so the round trip must drop them to
                # recover the original row shapes.
                h.rows.append({
                    k: _maybe_num(v) for k, v in row.items()
                    if k not in ("", None) and v != ""
                })
        return h

    def merge_resumed(self, rows, *, key: str = "round") -> int:
        """Fold per-round rows from a RESUMED run into this history,
        enforcing the same monotonic round watermark the telemetry
        resume path uses (dopt.obs): rows at rounds this history
        already holds are dropped (the continuous prefix wins — no
        duplicates), and the first genuinely new row must CONTINUE the
        sequence (a gap raises — a missing round means the resume lost
        data).  Returns the number of rows appended."""
        last = -1
        for r in self.rows:
            if key in r and isinstance(r[key], int):
                last = max(last, r[key])
        appended = 0
        for r in rows:
            t = r.get(key)
            if not isinstance(t, int):
                raise ValueError(
                    f"merge_resumed: row without an int {key!r}: {r!r}")
            if t <= last:
                continue
            if t != last + 1:
                raise ValueError(
                    f"merge_resumed: round gap {last} -> {t} (the resumed "
                    "stream is missing rounds)")
            self.rows.append(dict(r))
            last = t
            appended += 1
        return appended


def time_to_target(history: "History", *, target: float,
                   key: str = "avg_test_acc",
                   seconds_per_round: float | None = None) -> dict[str, Any]:
    """The north-star meter (BASELINE.json): first round at which
    ``key`` reaches ``target``, and — given a measured per-round
    wall-clock — the implied time-to-target.

    Returns {reached, round, rounds, seconds} where ``round`` is the
    history row's round number, ``rounds`` counts rows up to and
    including it, and ``seconds`` is rounds * seconds_per_round (None
    when no rate is supplied).  Rows without ``key`` (eval-skipped
    rounds) are passed over.
    """
    for i, row in enumerate(history.rows):
        v = row.get(key)
        if v is not None and v >= target:
            rounds = i + 1
            return {
                "reached": True,
                "round": row.get("round", i),
                "rounds": rounds,
                "seconds": (None if seconds_per_round is None
                            else rounds * seconds_per_round),
            }
    return {"reached": False, "round": None, "rounds": None, "seconds": None}


def _scalar(v: Any) -> Any:
    """Unwrap 0-d arrays / jax scalars so rows are plain JSON-able."""
    try:
        import numpy as np
        if hasattr(v, "item") and getattr(v, "ndim", None) in (0, None):
            return v.item()
        if isinstance(v, np.generic):
            return v.item()
    except Exception:
        pass
    return v


def _maybe_num(v: str) -> Any:
    try:
        f = float(v)
        return int(f) if f.is_integer() and "." not in v else f
    except (TypeError, ValueError):
        return v


def trimmed_stats(values) -> tuple[float, float, list[float]]:
    """Outlier-hardened reduction of per-window throughput samples
    (shared by bench.py and scripts/bench_seqlm.py): with >= 4 samples
    the min and max are DISCARDED (a shared host throws occasional
    stalls that poison a plain max−min spread), then
    (median, spread_pct, kept) over the survivors; spread_pct =
    (max−min)/median·100 of the kept set."""
    import statistics

    vals = sorted(float(v) for v in values)
    kept = vals[1:-1] if len(vals) >= 4 else vals
    med = statistics.median(kept)
    spread = 100.0 * (kept[-1] - kept[0]) / med if med > 0 else 0.0
    return med, spread, kept
