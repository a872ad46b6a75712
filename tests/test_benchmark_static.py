"""The benchmark's static tests in tier-1: ``BENCHMARK.json`` against
the contract's rules, the operation count and every op file by hand (no
chip, no trainer, two seconds), so that a broken entry or count fails
its tests and not its chip check.  The functions are
``benchmark/tests``' own, collected here under their names.

Then the statics of the ``laguna-xs2`` configuration: its file against
the catalog row it was drawn from, against the program's preset, and
against its own layer list.
"""

import json
from pathlib import Path

import pytest

from benchmark import flops
from benchmark.tests.test_benchmark_json import (  # noqa: F401
    test_configs_resolve, test_file_names_under_paths, test_metrics,
    test_no_cell_names_in_code, test_the_two_gossip32_files_are_one_job,
    test_top_level_keys, test_workloads_resolve)
from benchmark.tests.test_flops import (  # noqa: F401
    test_collective_bytes_counts_each_async_pair_once,
    test_model1_forward_by_hand, test_peaks_table_has_no_default,
    test_resnet18_forward_by_hand,
    test_round_flops_counts_training_three_times)
from benchmark.tests.test_ops import (  # noqa: F401
    test_every_op_file_has_both_functions_and_its_formula, test_op_by_hand,
    test_seq_toy_by_hand,
    test_seq_toy_layer_list_counts_the_reference_parameters,
    test_unknown_op_names_the_missing_file)

ROOT = Path(__file__).resolve().parents[1]
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
CONFIG = json.loads((ROOT / "benchmark/configs/laguna-xs2.json").read_text())
TRAFFIC = json.loads(
    (ROOT / "benchmark/traffic/localsgd2-t4096.json").read_text())
# The floors of the model-configs guide, section 4.
FLOORS = {"num_hidden_layers": 5, "experts_held": 8, "vocab_rows": 12544}


def test_laguna_file_holds_every_published_key():
    """Every key of the catalog row's ``config`` stands at the top level
    of the file under the same name and value, but for the keys listed in
    ``reduced`` — none of which is a width."""
    if not CATALOG.exists():
        pytest.skip("no catalog on this machine")
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines())
               if r["name"] == "Laguna-XS.2")
    assert CONFIG["source"].startswith(row["source_url"])
    differ = {k for k, v in row["config"].items() if CONFIG.get(k) != v}
    assert differ == {"num_hidden_layers"} <= set(CONFIG["reduced"])
    assert not any(k.endswith(("_dim", "_rank", "_size")) or "width" in k
                   for k in CONFIG["reduced"])
    assert CONFIG["published"]["num_hidden_layers"] \
        == row["config"]["num_hidden_layers"]


def test_laguna_cut_is_stated_and_at_the_floors():
    assert CONFIG["reduced"] == list(FLOORS)
    assert {k: CONFIG[k] for k in FLOORS} == FLOORS
    assert 8 * CONFIG["vocab_rows"] == CONFIG["vocab_size"]
    assert "32 chips" in CONFIG["deployment"]
    model, decoder = CONFIG["model"], CONFIG["model"]["decoder"]
    assert model["num_classes"] == CONFIG["vocab_rows"]
    assert model["input_shape"] == [4096]
    assert decoder["experts_held"] == CONFIG["experts_held"]
    # What runs is what the top level states, key for key.
    for key, value in decoder.items():
        if key != "expert_offset":
            assert CONFIG[key] == value, key
    # One whole period after the dense layer, every kind of layer in it.
    n = decoder["num_hidden_layers"]
    assert decoder["mlp_layer_types"][:n] == ["dense"] + ["sparse"] * 4
    assert decoder["layer_types"][:n] == (
        ["full_attention"] + ["sliding_attention"] * 3 + ["full_attention"])
    assert decoder["num_attention_heads_per_layer"][:n] == [48, 64, 64, 64, 48]


def test_laguna_layer_list_counts_the_parameters_and_the_work():
    layers = CONFIG["layers"]
    assert flops.param_count(layers) == CONFIG["parameters"] == 389_634_048
    tokens = CONFIG["model"]["input_shape"][0]
    per_token = flops.forward_flops(layers) / 2 / tokens
    assert round(per_token / 1e6, 1) == 340.3
    # one row trained: 3 forward passes' worth
    assert round(flops.round_flops(layers, train_samples=1,
                                   eval_forwards=0) / 1e12, 2) == 8.36
    experts = [x for x in layers if "held" in x]
    assert len(experts) == 12 and all(
        x["held"] == 8 and x["active"] == 8 * 8 / 256 for x in experts)


def test_laguna_states_every_guarantee_the_shipped_configs_state():
    for name in ("resnet18-cifar10", "model1-mnist"):
        other = json.loads(
            (ROOT / f"benchmark/configs/{name}.json").read_text())
        assert set(other["guarantees"]) <= set(CONFIG["guarantees"])
    g = CONFIG["guarantees"]
    assert (g["param_dtype"], g["compute_dtype"]) == ("float32", "bfloat16")
    assert (g["mixing"], g["fused_update"], g["prefetch"]) \
        == ("sync", "off", "off")


def test_laguna_preset_is_the_benchmark_configuration():
    """``python -m dopt.run --preset laguna-localsgd2`` trains what the
    cell measures: the same decoder, data, optimizer and gossip job."""
    from benchmark import adapter
    from dopt.presets import get_preset

    cell = adapter.build_config("cell", CONFIG, TRAFFIC, seed=28, chips=1)
    preset = get_preset("laguna-localsgd2")
    assert preset.model == cell.model
    assert preset.optim == cell.optim
    assert preset.data == cell.data
    for field in ("algorithm", "topology", "mode", "self_weight", "local_ep",
                  "local_bs"):
        assert getattr(preset.gossip, field) == getattr(cell.gossip, field)


def test_laguna_traffic_is_the_named_one():
    assert TRAFFIC["data"]["num_users"] == 2
    assert TRAFFIC["data"]["synthetic_train_size"] == 16     # 8 rows a worker
    assert (TRAFFIC["gossip"]["local_bs"], TRAFFIC["gossip"]["local_ep"]) \
        == (1, 1)
    assert TRAFFIC["parity"] == {"rounds": 2, "steps_per_epoch": 2}
    assert TRAFFIC["optim"] == {"lr": 0.01, "momentum": 0.9}
