"""``preroute_ms`` (the SmallThinker cell's routing before the attention)
on synthetic traces, and the cell's configuration and traffic files
against the counts they promise: the window binds on three of four
layers at 8,192 positions, a round is H = 4 steps of one row a worker,
and a held expert is handed 768 tokens a step."""

import json
from pathlib import Path

import pytest

from benchmark import flops
from benchmark.context import LayerInput
from benchmark.layer_metrics import preroute_ms, route_ms
from benchmark.ops import attention
from benchmark.trace_reduce import Op, Reduced

ROOT = Path(__file__).resolve().parents[2]
CELL = "smallthinker-21b.localsgd2.t8192"
CONFIG = json.loads(
    (ROOT / "benchmark/configs/smallthinker-21b-a3b.json").read_text())
TRAFFIC = json.loads(
    (ROOT / "benchmark/traffic/localsgd2-t8192-h4.json").read_text())
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
ROUNDS = 2


def ops_of(*spans):
    """Ops one after another: (name, ms, name stack) each."""
    out, t = [], 0.0
    for name, ms, stack in spans:
        out.append(Op(start=t, end=t + ms * 1e6, name=name, stack=stack))
        t += ms * 1e6 + 1e6
    return out


def run_of(ops):
    return LayerInput(
        reduced=Reduced(window=(0.0, 1e12), devices={"/device:TPU:0": ops},
                        host_spans=[]),
        rounds=ROUNDS, chips=1, device_kind="TPU v5 lite", config=CONFIG,
        traffic=TRAFFIC, samples_per_round=8,
        host_span_s={"round_dispatch": 1.0}, compile_s=0.0, round_hlo="",
        peak_bytes=[0])


ROUTE = "jit(round_fn)/dopt_local/jvp(vmap(jit(layer)))/dopt_moe/dopt_route"


def test_preroute_reads_its_scope_inside_the_route_s():
    """The scope's ops a round; ``route_ms`` holds them too (they run
    inside ``dopt_route``), and the layout's ops outside it are not
    counted."""
    run = run_of(ops_of(
        ("fusion.1", 3.0, ROUTE + "/dopt_preroute/softmax"),
        ("fusion.2", 1.0, ROUTE + "/dopt_preroute/top_k"),
        ("scatter.3", 5.0, ROUTE + "/scatter"),
        ("fusion.4", 7.0, "jit(round_fn)/dopt_local/dopt_attn/dot")))
    assert preroute_ms.read(run) == pytest.approx(4.0 / ROUNDS)
    assert route_ms.read(run) == pytest.approx(9.0 / ROUNDS)


def test_preroute_is_none_without_its_scope():
    """A program whose router runs after the attention (every other cell,
    the parent of this scope) leaves the metric out, and so does the
    rehearsal, which has no device plane."""
    run = run_of(ops_of(("fusion.3", 5.0, ROUTE + "/scatter")))
    assert preroute_ms.read(run) is None
    run.reduced = None
    assert preroute_ms.read(run) is None


def test_the_metric_is_listed_for_its_cell_alone():
    metric = {m["name"]: m for m in BENCH["per_layer"]}["preroute_ms"]
    assert metric["workloads"] == [CELL]
    assert metric["moves"] == "round_ms_p50"
    listed = [m["name"] for m in BENCH["per_layer"]
              if CELL in m.get("workloads", ())]
    assert "mlp_ms" not in listed and "shared_ms" not in listed
    for name in ("attn_ms", "moe_ms", "route_ms", "rope_ms",
                 "attn_kernel_roofline", "moe_kernel_roofline"):
        assert name in listed, name


def test_the_window_binds_on_three_of_four_layers():
    """Layer 0 is full (NoPE), layers 1-3 banded to 4,096 of 8,192
    positions: the counted attention is 29.4 M multiply-adds a token in
    the full layer and 22.0 M in each banded one."""
    layers = [layer for layer in CONFIG["layers"]
              if layer["op"] == "attention"]
    assert [layer["window"] for layer in layers] == [None, 4096, 4096, 4096]
    assert {(layer["heads"], layer["head_dim"], layer["positions"])
            for layer in layers} == {(28, 128, 8192)}
    full, banded = attention.macs(layers[0]), attention.macs(layers[1])
    assert round(full / 8192 / 1e5) / 10 == 29.4
    assert round(banded / 8192 / 1e5) / 10 == 22.0
    assert banded < full


def test_the_configuration_counts_its_worker():
    """370,547,200 parameters a worker from the layer list; about 246 M
    multiply-adds a token forward; the held experts' three products a
    layer at 6 x 8 / 64 = 0.75 of a token each."""
    assert flops.param_count(CONFIG["layers"]) == CONFIG["parameters"] \
        == 370_547_200
    per_token = flops.forward_flops(CONFIG["layers"]) / 2 / 8192
    assert 246e6 < per_token < 246.5e6
    held = [layer for layer in CONFIG["layers"] if layer.get("held")]
    assert len(held) == 12
    assert {(layer["held"], layer["active"]) for layer in held} == {(8, 0.75)}


def test_the_traffic_is_four_steps_of_one_row_a_worker():
    """H = 4: two workers with 4 rows each, one row a step, one epoch a
    round; 65,536 tokens a round.  A held expert sees 8,192 x 6 / 64 =
    768 tokens a step in expectation."""
    users = TRAFFIC["data"]["num_users"]
    rows = TRAFFIC["data"]["synthetic_train_size"] // users
    gossip = TRAFFIC["gossip"]
    steps = rows // gossip["local_bs"] * gossip["local_ep"]
    assert (users, rows, steps) == (2, 4, 4)
    assert users * steps * gossip["local_bs"] * 8192 == 65_536
    decoder = CONFIG["model"]["decoder"]
    assert 8192 * decoder["moe_num_active_primary_experts"] \
        // decoder["moe_num_primary_experts"] == 768
    assert TRAFFIC["eval"] == "none" and TRAFFIC["parity"]["rounds"] == 2
