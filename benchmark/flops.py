"""Operations a configured job REQUIRES, from shapes: the multiply-adds
of the layers listed in the configuration file's ``layers``.  Each
layer names its ``op``, and an op is one file, ``ops/<op>.py``, with
``macs(layer)`` (multiply-adds of ONE forward pass of ONE sample) and
``params(layer)``, its formula in its docstring: a later PR adds an op
by adding a file.  Norms, activations, pooling, the loss and the
optimizer are left out (under 1% of either convolutional model here),
and recomputed or padded work never counts.  A multiply-add is 2
operations; training a sample is 3 forward passes' worth (forward,
gradient by input, gradient by weight).

XLA's cost analysis is not used: it counts what the compiler lowered,
not what the algorithm needs.
"""

from __future__ import annotations

import importlib
import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def load_op(name: str):
    """``ops/<name>.py``."""
    try:
        return importlib.import_module(f"benchmark.ops.{name}")
    except ModuleNotFoundError as e:
        if e.name != f"benchmark.ops.{name}":
            raise
        raise ValueError(
            f"unknown layer op {name!r}: there is no benchmark/ops/{name}.py "
            "(macs(layer), params(layer))") from None


def layer_macs(layer: dict) -> int:
    """Multiply-adds of one forward pass of one sample through a layer."""
    return load_op(layer["op"]).macs(layer)


def layer_params(layer: dict) -> int:
    return load_op(layer["op"]).params(layer)


def forward_flops(layers: list[dict]) -> int:
    return 2 * sum(layer_macs(layer) for layer in layers)


def param_count(layers: list[dict]) -> int:
    return sum(layer_params(layer) for layer in layers)


def round_flops(layers: list[dict], *, train_samples: int,
                eval_forwards: int) -> int:
    """Operations one round of the job requires: every trained sample
    costs 3 forward passes, every evaluated one 1."""
    return forward_flops(layers) * (3 * train_samples + eval_forwards)


def device_peaks(device_kind: str) -> dict:
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(
            f"device kind {device_kind!r} is not in {PEAKS_FILE.name}; "
            "add it with its source, there is no default")
    return table[device_kind]
