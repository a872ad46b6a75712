"""Operations a configured job REQUIRES, from shapes: the matrix
multiplications of the convolutions and dense layers listed in the
configuration file's ``layers``.  Norms, activations, pooling, the loss
and the optimizer are left out (under 1% of either model here), and
recomputed or padded work never counts.  A multiply-add is 2 operations;
training a sample is 3 forward passes' worth (forward, gradient by
input, gradient by weight).

XLA's cost analysis is not used: it counts what the compiler lowered,
not what the algorithm needs.
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def layer_macs(layer: dict) -> int:
    """Multiply-adds of one forward pass of one sample through a layer."""
    if layer["op"] == "conv":
        h, w = layer["out_hw"]
        return h * w * layer["k"] ** 2 * layer["cin"] * layer["cout"]
    if layer["op"] == "dense":
        return layer["cin"] * layer["cout"]
    raise ValueError(f"unknown layer op {layer['op']!r}")


def layer_params(layer: dict) -> int:
    if layer["op"] == "conv":
        n = layer["k"] ** 2 * layer["cin"] * layer["cout"]
        # A normalised convolution carries the norm's scale and bias.
        extra = (layer["cout"] if layer["bias"] else 0) + (
            2 * layer["cout"] if layer["norm"] else 0)
        return n + extra
    if layer["op"] == "dense":
        return layer["cin"] * layer["cout"] + (
            layer["cout"] if layer["bias"] else 0)
    raise ValueError(f"unknown layer op {layer['op']!r}")


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
