"""What PR 35 brought as files: the reader of the lightning indexer's
score kernels (``index_kernel_roofline``) on the hand-made trace of
``test_anatomy_metrics``, its required operations and bytes by hand for
the ``keye-vl2-30b-a3b`` configuration, and that ``index_ms`` and
``attn_ms`` find the kernels by their names while ``attend_ms`` and
``select_ms`` do not."""

import dataclasses
import json
from pathlib import Path

import pytest

from benchmark.layer_metrics import index_kernel_roofline as roof
from benchmark.tests.test_anatomy_metrics import (HOST, OLD_HOST, chip,
                                                  layer_input, op, read,
                                                  reduced)
from benchmark.tests.test_sparse_attention_metrics import CONFIG, sparse_chip

ROOT = Path(__file__).resolve().parents[2]
CELL = "keye-vl2.localsgd2.t8192"


def index_chip(scale=1.0):
    """``chip()`` with one block of the indexed attention inside each
    round's local while: the index kernels forward (with no name stack
    at all), in the recompute and backward (with theirs), the selection
    and the alignment term under ``dopt_index`` around them, and one
    kernel of the attention's body."""
    j = "jit(round_fn)/dopt_local/while/body/closed_call/"
    a = "checkpoint/dopt_attn/while/body/closed_call/checkpoint/"
    b = j + "transpose(jvp(dopt_attn))/" + a
    ops = chip(scale)
    for r in range(3):
        t = r * 1000e6
        ops += [
            op(t + 100e6, t + 106e6 * scale, "dopt_attn_dopt_index_fwd.7"),
            op(t + 106e6, t + 121e6, "while.4",
               j + a + "dopt_index/dopt_select/while"),
            op(t + 121e6, t + 141e6, "dopt_attn_dopt_attend_fwd.3",
               j + a + "dopt_attend/dopt_attn_dopt_attend_fwd/pallas_call"),
            op(t + 141e6, t + 146e6, "fusion.9", j + a + "dopt_index/xlogy"),
            op(t + 146e6, t + 152e6, "dopt_attn_dopt_index_fwd.8",
               b + "rematted_computation/dopt_index/jit(_index_forward)/"
               "dopt_attn_dopt_index_fwd/pallas_call"),
            op(t + 152e6, t + 164e6, "dopt_attn_dopt_index_bwd.9",
               b + "dopt_index/jit(_index_backward)/"
               "dopt_attn_dopt_index_bwd/pallas_call"),
            op(t + 164e6, t + 170e6, "fusion.11", b + "dopt_index/transpose"),
        ]
    return ops


RUN = dataclasses.replace(
    layer_input(reduced({"/device:TPU:0": index_chip(),
                         "/device:TPU:1": index_chip(0.9)}), HOST),
    config=CONFIG, samples_per_round=4, chips=1)


@pytest.mark.parametrize("metric, value", [
    ("index_ms", 50.0),       # 100..121, 141..170: the kernels by name
    ("select_ms", 15.0),      # no kernel counts there
    ("attend_ms", 20.0),      # nor there
    ("attn_ms", 70.0),        # all of it lies in dopt_attn
    ("remat_ms", 6.0)])       # the recompute's kernel carries its stack
def test_the_scopes_find_the_kernels(metric, value):
    assert read(metric, RUN) == pytest.approx(value)


def test_roofline_share_by_hand():
    """Four layers' index scores for the round's four rows: one product
    of 16 heads of 64 over the causal triangle forward and two backward;
    24 ms of kernels a round.  The operations bound it."""
    layers = [x for x in CONFIG["layers"]
              if x["name"].endswith(".indexer.scores")]
    assert len(layers) == 4 and not any(x.get("window") for x in layers)
    t, heads, dim = 8192, 16, 64
    assert (CONFIG["sa_config"]["indexer_num_heads"],
            CONFIG["sa_config"]["indexer_head_dim"]) == (heads, dim)
    triangle = t * (t + 1) // 2
    ops = 3 * 2 * heads * dim * triangle                  # a row and layer
    moved = (2 * (heads + 1) * t * dim + 4 * t * heads + 2 * 4 * triangle)
    assert all(roof.kernel_flops(x) == ops for x in layers)
    assert all(roof.required_bytes(x, heads, dim) == moved for x in layers)
    assert ops / 197e12 > 2 * moved / 819e9
    want = 100 * 4 * 4 * (ops / 197e12) / 24e-3
    assert read("index_kernel_roofline", RUN) == pytest.approx(want)
    assert 50.0 < read("index_kernel_roofline", RUN) < 100.0


def test_nothing_in_the_rehearsal_before_the_spans_or_without_the_kernels():
    """``None``, so that the line leaves the metric out: the parent's
    program has the scope and no such kernel."""
    assert read("index_kernel_roofline", layer_input(None, HOST)) is None
    assert read("index_kernel_roofline", layer_input(
        reduced({"/device:TPU:0": index_chip()}), OLD_HOST)) is None
    for ops in (chip(), sparse_chip()):
        assert read("index_kernel_roofline", dataclasses.replace(
            layer_input(reduced({"/device:TPU:0": ops}), HOST),
            config=CONFIG)) is None
    # a configuration with no indexer: nothing to hold the time to
    for without in ({**CONFIG, "layers": [
            x for x in CONFIG["layers"] if "indexer" not in x["name"]]},
            {k: v for k, v in CONFIG.items() if k != "sa_config"}):
        assert read("index_kernel_roofline",
                    dataclasses.replace(RUN, config=without)) is None


def test_benchmark_json_lists_the_metric_for_the_cell():
    entry = next(m for m in json.loads(
        (ROOT / "BENCHMARK.json").read_text())["per_layer"]
        if m["name"] == "index_kernel_roofline")
    assert entry == {
        "name": "index_kernel_roofline", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "round program",
        "moves": "round_ms_p50", "workloads": [CELL]}
