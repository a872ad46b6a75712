"""``BENCHMARK.json`` against the contract's static rules, and the
data-driven promise: every name resolves to a file that exists."""

import importlib
import json
import re
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def one_line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmark"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(BENCH["command"]) <= 32
    assert all(one_line(w) and not w.startswith("/") and ".." not in w
               for w in BENCH["command"])
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_configs_resolve():
    names = [c["name"] for c in BENCH["configs"]]
    assert len(set(names)) == len(names)
    files = [c["file"] for c in BENCH["configs"]]
    assert len(set(files)) == len(files)
    used = {w["config"] for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert one_line(c["source"]) and one_line(c["why"])
        assert c["file"].startswith("benchmark/")
        body = json.loads((ROOT / c["file"]).read_text())
        assert body["name"] == c["name"]
        assert body["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
        for key in ("model", "data", "optim", "guarantees", "layers",
                    "parameters", "reference", "assumed"):
            assert key in body, key
        assert (ROOT / "benchmark" / "reference_models"
                / f"{body['reference']}.py").is_file()


def test_workloads_resolve():
    names = [w["name"] for w in BENCH["workloads"]]
    assert 2 <= len(names) <= 24 and len(set(names)) == len(names)
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    configs = {c["name"] for c in BENCH["configs"]}
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert one_line(w["why"])
        traffic = json.loads((ROOT / "benchmark" / "traffic"
                              / f"{w['traffic']}.json").read_text())
        assert traffic["name"] == w["traffic"]
        assert traffic["engine"] in traffic
        for key in ("data", "eval", "eval_forwards_per_round",
                    "rounds_per_call", "warmup_calls", "trace_calls",
                    "loss_key", "loss_round", "parity"):
            assert key in traffic, key
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(names) // 4)


def test_metrics():
    cells = {w["name"] for w in BENCH["workloads"]}
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(set(names)) == len(names)
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e and one_line(m["layer"])
        assert m["source"] in SOURCES
        reader = importlib.import_module(
            f"benchmark.layer_metrics.{m['name']}")
        assert callable(reader.read)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    for cell in cells:
        on = lambda m: cell in m.get("workloads", cells)
        assert on(e2e["setup_s"])
        assert sum(on(m) for m in BENCH["end_to_end"]) >= 2
        assert any(on(m) and on(e2e[m["moves"]]) for m in BENCH["per_layer"])


def test_file_names_under_paths():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    skip = ("__pycache__", "/out/")
    for p in (ROOT / "benchmark").rglob("*"):
        rel = p.relative_to(ROOT).as_posix()
        if p.is_file() and not any(s in rel for s in skip):
            assert ok.match(rel), rel


def test_no_cell_names_in_code():
    """The harness finds everything by name: no ``if workload == ...``."""
    cells = [w["name"] for w in BENCH["workloads"]]
    for p in (ROOT / "benchmark").glob("*.py"):
        text = p.read_text()
        for cell in cells:
            assert cell not in text, (p.name, cell)


def test_the_two_gossip32_files_are_one_job():
    a, b = (json.loads((ROOT / "benchmark" / "traffic" / f).read_text())
            for f in ("gossip32-random.json", "gossip32-random-mesh4.json"))
    differ = {k for k in a if a[k] != b[k]}
    assert differ <= {"name", "what", "loss_round"}
