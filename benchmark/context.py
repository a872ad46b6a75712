"""What a per-layer metric reader is handed."""

from __future__ import annotations

import dataclasses
from typing import Any


@dataclasses.dataclass
class LayerInput:
    """One traced run, reduced.  ``reduced`` is ``None`` in the CPU
    rehearsal (no device plane to read): a reader that needs it returns
    ``None`` and the harness leaves its metric out."""

    reduced: Any                 # trace_reduce.Reduced | None
    rounds: int                  # rounds inside the traced window
    chips: int
    device_kind: str
    config: dict                 # the cell's configuration file
    traffic: dict                # the cell's traffic file
    samples_per_round: int       # worker-samples trained a round
    host_span_s: dict            # trainer.timers totals inside the window
    compile_s: float             # whole process: compile or cache load
    round_hlo: str               # the compiled round program's HLO text
    peak_bytes: list             # per used chip, after the window
