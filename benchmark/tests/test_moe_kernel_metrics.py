"""What PR 34 brought as files: the reader of the held experts'
grouped-matmul kernels (``moe_kernel_roofline``) on the hand-made trace of
``test_anatomy_metrics``, its required operations and bytes by hand for
both decoder configurations, and that ``moe_ms`` and ``route_ms`` find
the kernels by their names."""

import dataclasses
import json
from pathlib import Path

import pytest

from benchmark.layer_metrics import moe_kernel_roofline as roof
from benchmark.tests.test_anatomy_metrics import (HOST, OLD_HOST, chip,
                                                  layer_input, op, read,
                                                  reduced)

ROOT = Path(__file__).resolve().parents[2]
CELLS = {"laguna-xs2": "localsgd2-t4096", "keye-vl2-30b-a3b": "localsgd2-t8192"}
ROWS = {"laguna-xs2": 16, "keye-vl2-30b-a3b": 4}     # a round, both workers


def files(config):
    return (json.loads((ROOT / f"benchmark/configs/{config}.json").read_text()),
            json.loads((ROOT / f"benchmark/traffic/{CELLS[config]}.json"
                        ).read_text()))


def moe_chip(scale=1.0):
    """``chip()`` with one expert layer inside each round's local while:
    the router and the slots' layout under ``dopt_route``, the forward
    kernel with no name stack at all, the backward's two with theirs."""
    j = "jit(round_fn)/dopt_local/while/body/closed_call/checkpoint/"
    ops = chip(scale)
    for r in range(3):
        t = r * 1000e6
        ops += [
            op(t + 100e6, t + 110e6, "fusion.7", j + "dopt_moe/dopt_route/top_k"),
            op(t + 110e6, t + 114e6, "scatter.2",
               j + "dopt_moe/dopt_route/scatter"),
            op(t + 114e6, t + 120e6, "convert.5",
               j + "dopt_moe/convert_element_type"),
            op(t + 120e6, t + 130e6 * scale, "dopt_moe_experts_fwd.3"),
            op(t + 130e6, t + 138e6, "dopt_moe_experts_dx.4",
               j + "dopt_moe/dopt_moe_experts_dx/pallas_call"),
            op(t + 138e6, t + 150e6, "dopt_moe_experts_dw.5",
               j + "dopt_moe/dopt_moe_experts_dw/pallas_call"),
            op(t + 150e6, t + 152e6, "gather.8",
               j + "dopt_moe/dopt_route/gather"),
        ]
    return ops


def run_of(config, devices=None):
    cfg, traffic = files(config)
    return dataclasses.replace(
        layer_input(reduced(devices or {"/device:TPU:0": moe_chip(),
                                        "/device:TPU:1": moe_chip(0.9)}),
                    HOST),
        config=cfg, traffic=traffic, samples_per_round=ROWS[config], chips=1)


@pytest.mark.parametrize("metric, value", [
    ("moe_ms", 52.0),         # 100..152: the kernels are found by name
    ("route_ms", 16.0),       # 100..114 and 150..152
    ("local_ms", 600.0)])
def test_the_scopes_find_the_kernels(metric, value):
    assert read(metric, run_of("laguna-xs2")) == pytest.approx(value)


@pytest.mark.parametrize("config", sorted(CELLS))
def test_roofline_share_by_hand(config):
    """30 ms of kernels a round of 16 (4) rows, a row a step: the weights'
    bytes bound ``laguna-xs2`` (128 slots an expert), the operations
    ``keye-vl2`` (512)."""
    cfg, traffic = files(config)
    run = run_of(config)
    assert traffic["gossip"]["local_bs"] == 1
    held = [x for x in cfg["layers"] if x.get("held")]
    assert len(held) == 12 and {x["held"] for x in held} == {8}
    d = cfg["hidden_size"]
    f, t = cfg["moe_intermediate_size"], cfg["model"]["input_shape"][0]
    active = cfg["num_experts_per_tok"] * 8 / cfg["num_experts"]
    assert {x["active"] for x in held} == {active}
    ops_s = 3 * 2 * 3 * t * active * d * f / 197e12         # a row and layer
    bytes_s = 2 * 4 * 3 * 8 * d * f / 819e9                 # a step and layer
    if config == "laguna-xs2":
        assert bytes_s > 2 * ops_s
    else:
        assert ops_s > bytes_s
    rows = ROWS[config]
    want = 100 * 4 * rows * max(ops_s, bytes_s) / 30e-3
    assert read("moe_kernel_roofline", run) == pytest.approx(want)
    assert read("moe_kernel_roofline", run) < 100.0
    # two rows a step: the leaves are read once for both
    two = dataclasses.replace(run, traffic={
        **traffic, "gossip": {**traffic["gossip"], "local_bs": 2}})
    assert read("moe_kernel_roofline", two) == pytest.approx(
        100 * 4 * max(rows * ops_s, rows / 2 * bytes_s) / 30e-3)


def test_nothing_in_the_rehearsal_before_the_spans_or_without_the_kernels():
    """``None``, so that the line leaves the metric out: the parent's
    program has no such kernel."""
    cfg, traffic = files("laguna-xs2")
    assert read("moe_kernel_roofline", layer_input(None, HOST)) is None
    assert read("moe_kernel_roofline", layer_input(
        reduced({"/device:TPU:0": moe_chip()}), OLD_HOST)) is None
    assert read("moe_kernel_roofline", run_of(
        "laguna-xs2", {"/device:TPU:0": chip()})) is None
    # a configuration that holds no experts: nothing to hold the time to
    dense = {**cfg, "layers": [x for x in cfg["layers"] if not x.get("held")]}
    assert read("moe_kernel_roofline", dataclasses.replace(
        run_of("laguna-xs2"), config=dense)) is None


def test_required_counts_of_one_product():
    layer = {"op": "matmul", "cin": 2048, "cout": 512, "positions": 4096,
             "held": 8, "active": 0.25}
    assert roof.required_flops(layer) == 6 * 1024 * 2048 * 512
    assert roof.required_bytes(layer) == 8 * 8 * 2048 * 512


def test_benchmark_json_lists_the_metric_for_the_decoder_cells():
    entry = next(m for m in json.loads(
        (ROOT / "BENCHMARK.json").read_text())["per_layer"]
        if m["name"] == "moe_kernel_roofline")
    assert entry == {
        "name": "moe_kernel_roofline", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "round program",
        "moves": "round_ms_p50",
        "workloads": ["laguna-xs2.localsgd2.t4096",
                      "keye-vl2.localsgd2.t8192"]}
