"""``flops.py`` against counts made by hand."""

import json
from pathlib import Path

import pytest

from benchmark import flops

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def layers(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())["layers"]


def test_model1_forward_by_hand():
    conv1 = 28 * 28 * 25 * 1 * 32          # 627,200
    conv2 = 14 * 14 * 25 * 32 * 64         # 10,035,200
    fc1 = 3136 * 512                       # 1,605,632
    fc2 = 512 * 10
    assert conv1 + conv2 + fc1 + fc2 == 12_273_152
    assert flops.forward_flops(layers("model1-mnist")) == 2 * 12_273_152
    assert flops.param_count(layers("model1-mnist")) == 1_663_370


def test_resnet18_forward_by_hand():
    stem = 32 * 32 * 27 * 64
    stage1 = 4 * 32 * 32 * 9 * 64 * 64
    stage = lambda hw, cin, c: (hw * hw * 9 * cin * c + 3 * hw * hw * 9 * c * c
                                + hw * hw * cin * c)
    macs = (stem + stage1 + stage(16, 64, 128) + stage(8, 128, 256)
            + stage(4, 256, 512) + 512 * 10)
    assert macs == 555_422_720
    assert flops.forward_flops(layers("resnet18-cifar10")) == 2 * macs
    assert flops.param_count(layers("resnet18-cifar10")) == 11_173_962


def test_round_flops_counts_training_three_times():
    one = [{"op": "dense", "name": "d", "cin": 10, "cout": 5, "bias": True}]
    assert flops.forward_flops(one) == 100
    assert flops.round_flops(one, train_samples=7, eval_forwards=4) == 100 * 25


def test_peaks_table_has_no_default():
    assert flops.device_peaks("TPU v5 lite")["flops_per_s_bf16"] == 197e12
    with pytest.raises(KeyError, match="no default"):
        flops.device_peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        flops.device_peaks("_source")


def test_collective_bytes_counts_each_async_pair_once():
    from benchmark.hlo_bytes import collective_bytes

    hlo = """
  %ag-start = (f32[8,4]{1,0}, f32[32,4]{1,0}) all-gather-start(f32[8,4]{1,0} %p), dimensions={0}
  %ag-done = f32[32,4]{1,0} all-gather-done(%ag-start)
  %ar = bf16[16]{0} all-reduce(bf16[16]{0} %x), to_apply=%add
  %f = f32[32,4]{1,0} fusion(%ag-done), kind=kLoop
"""
    got = collective_bytes(hlo)
    # clones of one collective share its channel id and count once
    cloned = hlo + """
  %ag.1 = f32[64,4]{1,0} all-gather(f32[16,4]{1,0} %q), channel_id=7, dimensions={0}
  %ag.1.clone = f32[64,4]{1,0} all-gather(f32[16,4]{1,0} %q), channel_id=7, dimensions={0}
  %ag.2 = f32[64,4]{1,0} all-gather(f32[16,4]{1,0} %r), channel_id=8, dimensions={0}
"""
    assert (collective_bytes(cloned)["all-gather"]
            == got["all-gather"] + 2 * 64 * 4 * 4)
    assert got["all-gather"] == (8 * 4 + 32 * 4) * 4
    assert got["all-reduce"] == 32
    assert got["total"] == got["all-gather"] + got["all-reduce"]
