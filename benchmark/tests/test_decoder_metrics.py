"""The decoder's per-layer metrics on the hand-made trace of
``test_anatomy_metrics``: ``attn_ms`` reads the ``dopt_attn`` scope
together with the fused attention kernels (no name stack: found by their
names), ``moe_ms`` / ``route_ms`` / ``head_ms`` their scopes, and
``attn_kernel_roofline`` holds the kernels' time against operations and
bytes counted by hand."""

import dataclasses
import json
from pathlib import Path

import pytest

from benchmark.layer_metrics import attn_kernel_roofline as roof
from benchmark.tests.test_anatomy_metrics import (HOST, OLD_HOST, chip,
                                                  layer_input, op, read,
                                                  reduced)

CONFIG = json.loads((Path(__file__).resolve().parents[1] / "configs"
                     / "laguna-xs2.json").read_text())


def decoder_chip(scale=1.0):
    """``chip()`` with a decoder step inside each round's local while."""
    j = "jit(round_fn)/dopt_local/while/body/closed_call/"
    ops = chip(scale)
    for r in range(3):
        t = r * 1000e6
        ops += [
            op(t + 100e6, t + 120e6, "fusion.7",
               j + "checkpoint/dopt_attn/dot_general"),
            # the kernels: named, no stack; the second overlaps the scope
            op(t + 120e6, t + 150e6 * scale, "splash_mqa_fwd_residuals.90"),
            op(t + 150e6, t + 170e6, "splash_mqa_dkv_no_residuals.20"),
            op(t + 165e6, t + 180e6, "fusion.8",
               j + "transpose(jvp(dopt_attn))/dot_general"),
            op(t + 200e6, t + 260e6, "fusion.9",
               j + "checkpoint/dopt_moe/td,edf->tef/dot_general"),
            op(t + 260e6, t + 270e6, "fusion.10",
               j + "checkpoint/dopt_moe/dopt_route/top_k"),
            op(t + 300e6, t + 325e6, "while.3", j + "dopt_head/while"),
        ]
    return ops


RUN = layer_input(reduced({"/device:TPU:0": decoder_chip(),
                           "/device:TPU:1": decoder_chip(0.9)}), HOST)


@pytest.mark.parametrize("metric, value", [
    ("attn_ms", 80.0),        # 100 .. 180, the union of scope and kernels
    ("moe_ms", 70.0), ("route_ms", 10.0), ("head_ms", 25.0)])
def test_value(metric, value):
    assert read(metric, RUN) == pytest.approx(value)
    # nested in the local phase, which they leave as it was
    assert read("local_ms", RUN) == pytest.approx(600.0)


@pytest.mark.parametrize("metric", ["attn_ms", "moe_ms", "route_ms",
                                    "head_ms", "attn_kernel_roofline"])
def test_nothing_in_the_rehearsal_or_before_the_spans(metric):
    assert read(metric, layer_input(None, HOST)) is None
    assert read(metric, layer_input(
        reduced({"/device:TPU:0": decoder_chip()}), OLD_HOST)) is None


def test_zero_or_nothing_on_a_program_without_them():
    run = layer_input(reduced({"/device:TPU:0": chip()}), HOST)
    for metric in ("attn_ms", "moe_ms", "route_ms", "head_ms"):
        assert read(metric, run) == 0.0
    assert read("attn_kernel_roofline", run) is None


def test_kernel_operations_and_bytes_by_hand():
    full = {"op": "attention", "heads": 48, "head_dim": 128,
            "positions": 4096, "window": None}
    band = {**full, "heads": 64, "window": 512}
    # full: sum_t (t + 1) = 4096 * 4097 / 2 keys; scores and values, a
    # multiply-add is 2 operations, forward plus twice that backward
    assert roof.kernel_flops(full) \
        == 3 * 2 * 2 * 48 * 128 * (4096 * 4097 // 2)
    # band: 512 * 513 / 2 keys for the first 512 queries, 512 each after
    assert roof.kernel_flops(band) \
        == 3 * 2 * 2 * 64 * 128 * (512 * 513 // 2 + 3584 * 512)
    # 6 q-shaped and 6 k-shaped bfloat16 arrays
    assert roof.kernel_bytes(full, 8) \
        == 2 * 6 * 4096 * 128 * (48 + 8)
    layers = [x for x in CONFIG["layers"] if x["op"] == "attention"]
    assert [x["window"] for x in layers] == [None, 512, 512, 512, None]
    assert sum(map(roof.kernel_flops, layers)) == 2 * roof.kernel_flops(
        full) + 3 * roof.kernel_flops(band)


def test_roofline_share():
    """30 + 20 ms of kernels a round on the busiest chip; the required
    operations bound the least time (197 TFLOP/s against 819 GB/s)."""
    run = dataclasses.replace(RUN, config=CONFIG, samples_per_round=16,
                              chips=1)
    layers = [x for x in CONFIG["layers"] if x["op"] == "attention"]
    least = sum(roof.kernel_flops(x) for x in layers) / 197e12
    assert all(roof.kernel_flops(x) / 197e12
               > roof.kernel_bytes(x, 8) / 819e9 for x in layers)
    assert read("attn_kernel_roofline", run) == pytest.approx(
        100 * least * 16 / 50e-3)
