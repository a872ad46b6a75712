"""Every op file of ``benchmark/ops/`` against arithmetic done by hand,
and the toy sequence configuration's layer list against its reference's
parameters."""

import json
from pathlib import Path

import pytest

from benchmark import flops

DATA = Path(__file__).resolve().parent / "data"
OPS = Path(__file__).resolve().parents[1] / "ops"


def seq_toy_layers():
    return json.loads((DATA / "configs" / "seq-toy.json").read_text())["layers"]


def test_every_op_file_has_both_functions_and_its_formula():
    names = sorted(p.stem for p in OPS.glob("*.py") if p.stem != "__init__")
    assert names == ["attention", "conv", "dense", "embedding", "matmul",
                     "scale"]
    for name in names:
        op = flops.load_op(name)
        assert callable(op.macs) and callable(op.params)
        assert "macs" in op.__doc__ and "params" in op.__doc__


def test_unknown_op_names_the_missing_file():
    with pytest.raises(ValueError, match=r"benchmark/ops/rotary\.py"):
        flops.layer_macs({"op": "rotary"})
    with pytest.raises(ValueError, match=r"benchmark/ops/rotary\.py"):
        flops.layer_params({"op": "rotary"})


@pytest.mark.parametrize("layer, macs, params", [
    # one matrix, once a sample: the old dense
    ({"op": "matmul", "cin": 10, "cout": 5, "bias": True}, 50, 55),
    # the same matrix at 7 positions
    ({"op": "matmul", "cin": 10, "cout": 5, "positions": 7}, 350, 50),
    # 8 experts held of 256 published, 8 a token: 8 * 8 / 256 = 0.25 of a
    # 2048 x 512 expert a position, 4096 positions
    ({"op": "matmul", "cin": 2048, "cout": 512, "positions": 4096, "held": 8,
      "active": 0.25}, 4096 * 2048 * 512 // 4, 8 * 2048 * 512),
    # held copies with a bias each
    ({"op": "matmul", "cin": 3, "cout": 4, "bias": True, "held": 5,
      "active": 2}, 24, 5 * 16),
    # full causal attention: 1 + 2 + ... + 6 = 21 keys
    ({"op": "attention", "heads": 4, "head_dim": 8, "positions": 6,
      "window": None}, 2 * 4 * 8 * 21, 0),
    # a window of 3: 1 + 2 + 3 + 3 + 3 + 3 = 15 keys
    ({"op": "attention", "heads": 4, "head_dim": 8, "positions": 6,
      "window": 3}, 2 * 4 * 8 * 15, 0),
    # a window wider than the sequence is full attention
    ({"op": "attention", "heads": 2, "head_dim": 4, "positions": 3,
      "window": 512}, 2 * 2 * 4 * 6, 0),
    # 48 query heads of 128 over 4,096 positions, window 512:
    # 512 * 513 / 2 + (4096 - 512) * 512 = 1,966,336 keys
    ({"op": "attention", "heads": 48, "head_dim": 128, "positions": 4096,
      "window": 512}, 2 * 48 * 128 * 1_966_336, 0),
    ({"op": "embedding", "rows": 12_544, "dim": 2048}, 0, 25_690_112),
    ({"op": "scale", "dim": 2048}, 0, 2048),
])
def test_op_by_hand(layer, macs, params):
    assert flops.layer_macs(layer) == macs
    assert flops.layer_params(layer) == params
    assert isinstance(flops.layer_macs(layer), int)


def test_seq_toy_by_hand():
    """12 positions, width 32, 4 query heads of 8 over 2 key/value heads."""
    projections = 12 * (32 * 32 + 32 * 16 + 32 * 16 + 32 * 32)     # 36,864
    window4 = 2 * 4 * 8 * (1 + 2 + 3 + 4 * 9)                      # 2,688
    full = 2 * 4 * 8 * (12 * 13 // 2)                              # 4,992
    dense_mlp = 12 * 3 * 32 * 64                                   # 73,728
    router = 12 * 32 * 8
    experts = 12 * 1 * 3 * 32 * 16          # 2 of 8 a token, 4 held: 1.0
    shared = 12 * 3 * 32 * 16
    head = 12 * 32 * 48
    macs = (2 * projections + window4 + full + dense_mlp + router + experts
            + shared + head)
    assert macs == 213_504
    layers = seq_toy_layers()
    assert flops.forward_flops(layers) == 2 * macs
    attn = 32 + 32 * 32 + 2 * 32 * 16 + 32 * 32                    # 3,104
    params = (48 * 32 + (attn + 32 + 3 * 32 * 64)
              + (attn + 32 + 32 * 8 + 4 * 3 * 32 * 16 + 3 * 32 * 16)
              + 32 + 32 * 48)
    assert params == 23_456 == flops.param_count(layers)
    # a round: 3 forward passes a trained row, 1 an evaluated one
    assert flops.round_flops(layers, train_samples=24, eval_forwards=8) \
        == 2 * macs * (3 * 24 + 8)


def test_seq_toy_layer_list_counts_the_reference_parameters():
    import jax

    from benchmark.tests.test_reference_seams import seq_toy

    config = json.loads((DATA / "configs" / "seq-toy.json").read_text())
    held = sum(x.size for x in jax.tree.leaves(seq_toy().init(0)))
    assert held == config["parameters"] == flops.param_count(config["layers"])
