"""What PR 32 brought as files: the four readers of a learned sparse
attention (``index_ms``, ``select_ms``, ``attend_ms``,
``sparse_attn_roofline``) on the hand-made trace of
``test_anatomy_metrics``, the operation and parameter count of the
``keye-vl2-30b-a3b`` configuration by hand, and its cell's rehearsal."""

import dataclasses
import json
from pathlib import Path

import pytest

from benchmark import flops
from benchmark.layer_metrics import attn_kernel_roofline as roof
from benchmark.tests.test_anatomy_metrics import (HOST, OLD_HOST, chip,
                                                  layer_input, op, read,
                                                  reduced)

ROOT = Path(__file__).resolve().parents[2]
CONFIG = json.loads((ROOT / "benchmark/configs/keye-vl2-30b-a3b.json"
                     ).read_text())
CELL = "keye-vl2.localsgd2.t8192"
METRICS = ("index_ms", "select_ms", "attend_ms", "sparse_attn_roofline")


def sparse_chip(scale=1.0):
    """``chip()`` with one block of the indexed attention inside each
    round's local while: index scores, the selection inside them, the
    attend body, its recompute and backward, the alignment term."""
    j = "jit(round_fn)/dopt_local/while/body/closed_call/"
    a = "checkpoint/dopt_attn/while/body/closed_call/checkpoint/"
    ops = chip(scale)
    for r in range(3):
        t = r * 1000e6
        ops += [
            op(t + 100e6, t + 110e6, "fusion.7", j + a + "dopt_index/dot_general"),
            op(t + 110e6, t + 125e6, "while.4",
               j + a + "dopt_index/dopt_select/while"),
            op(t + 125e6, t + 165e6 * scale, "fusion.8",
               j + a + "dopt_attend/grqd,gkd->grqk/dot_general"),
            op(t + 165e6, t + 170e6, "fusion.9", j + a + "dopt_index/xlogy"),
            op(t + 170e6, t + 190e6, "fusion.10",
               j + "transpose(jvp(dopt_attn))/" + a
               + "rematted_computation/dopt_attend/exp"),
            op(t + 190e6, t + 200e6, "fusion.11",
               j + "transpose(jvp(dopt_attn))/" + a + "dopt_index/transpose"),
        ]
    return ops


RUN = dataclasses.replace(
    layer_input(reduced({"/device:TPU:0": sparse_chip(),
                         "/device:TPU:1": sparse_chip(0.9)}), HOST),
    config=CONFIG, samples_per_round=2, chips=1)


@pytest.mark.parametrize("metric, value", [
    ("index_ms", 40.0),       # 100..125, 165..170, 190..200
    ("select_ms", 15.0),      # inside the index scope
    ("attend_ms", 60.0),      # 125..165 forward, 170..190 recomputed
    ("attn_ms", 100.0)])      # all of it lies in dopt_attn
def test_value(metric, value):
    assert read(metric, RUN) == pytest.approx(value)
    assert read("local_ms", RUN) == pytest.approx(600.0)


@pytest.mark.parametrize("metric", METRICS)
def test_nothing_in_the_rehearsal_before_the_spans_or_without_the_scopes(
        metric):
    """``None``, so that the line leaves the metric out: the parent's
    program has no such scope."""
    assert read(metric, layer_input(None, HOST)) is None
    assert read(metric, layer_input(
        reduced({"/device:TPU:0": sparse_chip()}), OLD_HOST)) is None
    assert read(metric, dataclasses.replace(layer_input(
        reduced({"/device:TPU:0": chip()}), HOST), config=CONFIG)) is None


def test_roofline_share_by_hand():
    """The required attention alone: the four layers with a window, a
    query attending min(t + 1, 2048) keys, three passes of two products;
    60 ms under the scope a round of two rows."""
    layers = [x for x in CONFIG["layers"] if x["op"] == "attention"]
    sparse = [x for x in layers if x.get("window")]
    assert len(layers) == 8 and len(sparse) == 4
    assert {x["name"].split(".", 1)[1] for x in sparse} == {"attention"}
    keys = 2048 * 2049 // 2 + (8192 - 2048) * 2048        # 14,681,088
    one = 3 * 2 * 2 * 32 * 128 * keys
    assert all(roof.kernel_flops(x) == one for x in sparse)
    assert all(one / 197e12 > roof.kernel_bytes(x, 4) / 819e9
               for x in sparse)
    assert read("sparse_attn_roofline", RUN) == pytest.approx(
        100 * (4 * one / 197e12) * 2 / 60e-3)
    assert read("sparse_attn_roofline", RUN) < 100.0
    # no layer with a window: nothing to hold the time against
    dense = {**CONFIG, "layers": [x for x in CONFIG["layers"]
                                  if not x.get("window")]}
    assert read("sparse_attn_roofline",
                dataclasses.replace(RUN, config=dense)) is None


def test_operations_and_parameters_by_hand():
    d, t, rows = 2048, 8192, 18992
    attention = d * 32 * 128 * 2 + 2 * d * 4 * 128              # q o, k v
    indexer = d * 16 * 64 + d * 64 + d * 16
    norms = 2 * d + 2 * 128 + 2 * 64
    layer = attention + indexer + norms + d * 128 + 8 * 3 * d * 768
    assert layer == 59_150_720
    assert flops.param_count(CONFIG["layers"]) == CONFIG["parameters"] \
        == 4 * layer + 2 * rows * d + d == 314_396_160
    causal = t * (t + 1) // 2
    band = 2048 * 2049 // 2 + (t - 2048) * 2048
    macs = (4 * (t * (attention + indexer + d * 128)
                 + t * 0.5 * 3 * d * 768                # 8 * 8 / 128 active
                 + 2 * 32 * 128 * band                  # the keys attended
                 + 16 * 64 * causal)                    # index scores, once
            + t * d * rows)
    assert flops.forward_flops(CONFIG["layers"]) == 2 * macs
    assert CONFIG["reduced"] == ["num_hidden_layers", "experts_held",
                                 "vocab_rows"]
    assert (CONFIG["num_hidden_layers"], CONFIG["experts_held"],
            CONFIG["vocab_rows"]) == (4, 8, rows)


def test_the_configuration_keeps_every_published_number():
    """Against the catalog's row, where the guides are installed: every
    number under its key, but the depth; nested groups whole."""
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if not catalog.is_file():
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, catalog.read_text().splitlines())
               if r["name"] == "Keye-VL-2.0-30B-A3B")
    assert row["source_url"] in CONFIG["source"]
    differ = {k for k, v in row["config"].items() if CONFIG.get(k) != v}
    assert differ == {"num_hidden_layers"}
    decoder = CONFIG["model"]["decoder"]
    assert {k for k, v in row["config"].items() if decoder.get(k) != v} \
        == {"num_hidden_layers"}


def test_the_cell_rehearses(monkeypatch, capsys):
    """The whole control flow of the new cell on the CPU: the toy of the
    configuration's ``rehearsal`` through the gossip engine, the window,
    the parity check against ``reference_models/keye_vl2.py``."""
    from benchmark import run

    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    rc = run.main(["--workload", CELL, "--seed", "3200000031", "--seconds",
                   "1", "--trace", "0", "--rehearse"])
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["would_report"] == ["round_ms_p50", "setup_s",
                                    "train_samples_per_s"]
    parity = line["compared"]["parity_error_moved"]
    assert parity["value"] < 1e-4 < parity["limit"]
