"""Rehearse ``chip_smoke.py`` on the CPU: every leg through the same
functions at tiny widths (chip time is budgeted — a leg that cannot even
run here must not be debugged there), the entry's refusal to run
without a TPU, and the compile-cache placement rule.
"""

from __future__ import annotations

import dataclasses
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

import chip_smoke
from dopt.config import CommConfig
from dopt.presets import get_preset

REPO = Path(__file__).resolve().parent.parent
MLP = ["--set", "model.model=mlp", "--set", "model.faithful=false"]
TINY_HEAD = ["--preset", chip_smoke.HEADLINE, "--synthetic-scale", "0.01",
             *MLP]


def _tiny(cfg, **data_kw):
    """A preset cut to an MLP on a few hundred samples."""
    return cfg.replace(
        model=dataclasses.replace(cfg.model, model="mlp", faithful=False),
        data=dataclasses.replace(cfg.data, synthetic_train_size=768,
                                 synthetic_test_size=128, **data_kw))


def test_cli_legs_gossip_and_federated():
    out = chip_smoke.leg_cli(["--preset", "baseline1", "--rounds", "2",
                              "--num-users", "4",
                              "--synthetic-scale", "0.005"])
    assert out["rounds"] == 2 and out["mesh"] == "{'workers': 4}"
    out = chip_smoke.leg_cli(["--preset", "baseline3", "--rounds", "2",
                              "--synthetic-scale", "0.01", *MLP])
    assert out["rounds"] == 2


def test_default_off_path_legs():
    assert chip_smoke.leg_cli(
        [*TINY_HEAD, "--rounds", "2", "--set",
         "gossip.update_sharding=scatter"])["rounds"] == 2
    assert chip_smoke.leg_cli(
        [*TINY_HEAD, "--rounds", "4", "--set", "gossip.prefetch=on",
         "--set", "gossip.block_rounds=2"])["rounds"] == 4
    cfg = _tiny(get_preset(chip_smoke.HEADLINE))
    out = chip_smoke.leg_codec(cfg, comm=CommConfig(
        codec="qsgd", chunk=64, min_codec_bytes=256))
    assert out["plan_kinds"] == ["q8"]
    out = chip_smoke.leg_fused(
        cfg, [*TINY_HEAD, "--rounds", "2", "--set",
              "gossip.fused_update=on", "--set", "mesh_devices=1"])
    # interpret mode on the CPU: nothing Mosaic-compiled, same numbers
    assert out["tpu_custom_calls"] == 0
    assert out["epilogue_max_abs_err"] < 1e-5


def test_trace_leg_reduces_a_cli_trace(tmp_path, monkeypatch):
    monkeypatch.setattr(chip_smoke, "OUT_DIR", tmp_path / "out")
    out = chip_smoke.leg_trace([*TINY_HEAD, "--rounds", "1"],
                               need_device_time=False)
    table = json.loads((tmp_path / "out/chip_smoke_trace.json").read_text())
    assert table["device"]["platform"] == "cpu"
    assert set(out["phase_us"]) == {"conv", "comm", "update"}
    # A CPU trace has no device plane; on the chip that is a failure.
    with pytest.raises(AssertionError, match="device self time"):
        chip_smoke.leg_trace([*TINY_HEAD, "--rounds", "1"],
                             need_device_time=True)


def test_consensus_leg():
    out = chip_smoke.leg_consensus(n=4, width=256)
    assert set(out["mean_drift"]) == {"mix_dense", "mix_dense_scatter",
                                      "fused_mix_update"}


@pytest.mark.parametrize("update_sharding", ["off", "scatter"])
def test_ring_parity_leg_on_four_virtual_devices(update_sharding):
    cfg = _tiny(chip_smoke.ring8_cfg())
    out = chip_smoke.leg_ring_parity(cfg, devices=4,
                                     update_sharding=update_sharding)
    assert out["mesh"] == "{'workers': 4}" and out["shift_ids"]


def test_leg_table_and_failure_accounting(capsys):
    assert "multichip-ring" not in chip_smoke.legs(1)
    assert {"multichip-baseline5", "multichip-ring",
            "multichip-ring-scatter"} <= set(chip_smoke.legs(4))
    with chip_smoke.CompileMeter() as meter:
        failed = chip_smoke.run_legs(
            {"boom": lambda: 1 / 0, "fine": lambda: {"rounds": 1}}, meter)
    assert failed == ["boom"]
    boom, fine = (json.loads(ln) for ln in
                  capsys.readouterr().out.splitlines())
    assert boom["ok"] is False and "ZeroDivisionError" in boom["error"]
    assert fine["ok"] is True and fine["platform"] == "cpu"


def test_entry_refuses_to_run_without_a_tpu():
    run = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")], cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True,
        text=True, timeout=120)
    assert run.returncode == 1
    assert run.stdout == ""                       # no result line
    assert "'cpu'" in run.stderr


_CACHE_CHILD = """
import json
import jax, jax.numpy as jnp
from dopt.utils.compile_cache import enable_compile_cache
hits = []
jax.monitoring.register_event_listener(
    lambda e, **kw: hits.append(e)
    if e == "/jax/compilation_cache/cache_hits" else None)
d = enable_compile_cache()
jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((64, 64))).block_until_ready()
print(json.dumps({"dir": d, "config": jax.config.jax_compilation_cache_dir,
                  "hits": len(hits)}))
"""


def _cache_child(checkout: Path, env_extra: dict) -> dict:
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "XLA_FLAGS")}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(checkout),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0", **env_extra)
    run = subprocess.run([sys.executable, "-c", _CACHE_CHILD], env=env,
                         cwd=checkout, capture_output=True, text=True,
                         timeout=120)
    assert run.returncode == 0, run.stderr[-2000:]
    return json.loads(run.stdout.splitlines()[-1])


def test_compile_cache_placement(tmp_path):
    # A stand-in checkout holding only the function under test, so the
    # default directory lands in tmp_path, not in the real tree.
    checkout = tmp_path / "checkout"
    (checkout / "dopt/utils").mkdir(parents=True)
    (checkout / "dopt/__init__.py").write_text("")
    (checkout / "dopt/utils/__init__.py").write_text("")
    shutil.copy(REPO / "dopt/utils/compile_cache.py",
                checkout / "dopt/utils/compile_cache.py")
    default = checkout / ".jax_cache"

    from dopt.utils.compile_cache import PROGRAM_METADATA_VERSION

    sub = f"meta-v{PROGRAM_METADATA_VERSION}"

    # Placed from outside: the versioned subdirectory of the given one
    # (the cache key ignores the programs' scope metadata; the directory
    # name does not), nothing else created.
    placed = tmp_path / "placed"
    got = _cache_child(checkout,
                       {"JAX_COMPILATION_CACHE_DIR": str(placed)})
    assert got["dir"] == got["config"] == str(placed / sub)
    assert [p.name for p in placed.iterdir()] == [sub]
    assert any((placed / sub).iterdir()) and not default.exists()

    # Not placed: the fixed <checkout>/.jax_cache/<version>; a second
    # process of the same command compiles from it.
    first = _cache_child(checkout, {})
    assert first["dir"] == first["config"] == str(default / sub)
    assert first["hits"] == 0 and any((default / sub).iterdir())
    assert _cache_child(checkout, {})["hits"] >= 1
