"""Model zoo: param-count parity with the reference, head semantics, loss."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dopt.models import build_model, count_params
from dopt.models.losses import accuracy, cross_entropy, l2_regulariser


def _init(model, shape):
    return model.init(jax.random.key(0), jnp.zeros((1, *shape)))["params"]


def test_model1_param_count_parity():
    # Reference models.py:5 comment — 1,663,370 params, arithmetic verified.
    params = _init(build_model("model1"), (28, 28, 1))
    assert count_params(params) == 1_663_370


def test_model3_param_count_parity():
    # Reference models.py:30 comment — 1,105,098 params.
    params = _init(build_model("model3", num_classes=10), (32, 32, 3))
    assert count_params(params) == 1_105_098


def test_faithful_returns_probabilities():
    m = build_model("model1", faithful=True)
    params = _init(m, (28, 28, 1))
    out = m.apply({"params": params}, jnp.ones((4, 28, 28, 1)))
    np.testing.assert_allclose(np.sum(out, axis=-1), 1.0, rtol=1e-5)
    assert np.all(out >= 0)


def test_corrected_head_returns_logits():
    m = build_model("model1", faithful=False)
    params = _init(m, (28, 28, 1))
    out = m.apply({"params": params}, jnp.ones((4, 28, 28, 1)))
    assert not np.allclose(np.sum(out, axis=-1), 1.0)


def test_double_softmax_loss_differs_from_corrected():
    # The faithful objective is NOT the standard CE — make sure we are
    # really reproducing the reference's bug.
    logits = jnp.array([[2.0, -1.0, 0.5]])
    labels = jnp.array([0])
    corrected = cross_entropy(logits, labels)
    faithful = cross_entropy(jax.nn.softmax(logits), labels)
    assert abs(float(corrected) - float(faithful)) > 0.1


def test_cross_entropy_weighted_mask():
    out = jnp.array([[5.0, 0.0], [0.0, 5.0], [9.9, 9.9]])
    y = jnp.array([0, 1, 0])
    w = jnp.array([1.0, 1.0, 0.0])
    full = cross_entropy(out[:2], y[:2])
    masked = cross_entropy(out, y, w)
    np.testing.assert_allclose(float(full), float(masked), rtol=1e-6)


def test_accuracy_mask():
    out = jnp.array([[1.0, 0.0], [1.0, 0.0], [0.0, 1.0]])
    y = jnp.array([0, 1, 1])
    assert float(accuracy(out, y)) == pytest.approx(2 / 3)
    assert float(accuracy(out, y, jnp.array([1.0, 1.0, 0.0]))) == pytest.approx(0.5)


def test_mlp_and_logistic():
    m = build_model("mlp", faithful=False)
    p = _init(m, (28, 28, 1))
    assert m.apply({"params": p}, jnp.ones((2, 28, 28, 1))).shape == (2, 10)
    lr = build_model("logistic", num_classes=2, faithful=False)
    plr = _init(lr, (123,))
    assert lr.apply({"params": plr}, jnp.ones((2, 123))).shape == (2, 2)
    assert count_params(plr) == 123 * 2 + 2
    assert float(l2_regulariser(plr, 0.0)) == 0.0


def test_resnet18_forward():
    m = build_model("resnet18", faithful=False)
    p = _init(m, (32, 32, 3))
    n = count_params(p)
    assert 10_000_000 < n < 12_000_000, n  # ~11.2M standard ResNet-18
    out = m.apply({"params": p}, jnp.ones((2, 32, 32, 3)))
    assert out.shape == (2, 10)


def test_build_model_unknown():
    with pytest.raises(ValueError, match="unknown model"):
        build_model("model2")


def test_faithful_conv_stack_has_no_activations():
    # The reference conv block is conv->pool->conv->pool with NO ReLU
    # (models.py:10-15); a linear conv stack commutes with scaling.
    import jax
    import jax.numpy as jnp
    m = build_model("model1", faithful=True)
    p = m.init(jax.random.key(1), jnp.zeros((1, 28, 28, 1)))["params"]

    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 28, 28, 1)), jnp.float32)
    # Idiomatic variant with the SAME params gives different outputs
    # (ReLU between convs) — guards against silently re-adding conv ReLUs.
    m2 = build_model("model1", faithful=False)
    out1 = m.apply({"params": p}, x)
    out2 = m2.apply({"params": p}, x)
    assert not np.allclose(np.asarray(out1), np.asarray(jax.nn.softmax(out2)), atol=1e-4)


def test_bf16_compute_mode_trains():
    # bf16 compute, fp32 params: forward emits reasonable values and a
    # short training run still learns on the virtual mesh.
    import jax
    import jax.numpy as jnp

    from dopt.models import build_model

    m = build_model("model1", dtype="bfloat16", faithful=False)
    params = m.init(jax.random.key(0), jnp.zeros((1, 28, 28, 1)))["params"]
    # params stay fp32 (bf16 is compute-only)
    assert all(p.dtype == jnp.float32 for p in jax.tree.leaves(params))
    out = m.apply({"params": params}, jnp.ones((2, 28, 28, 1)))
    # Corrected head: the logits layer computes in f32 even under bf16
    # compute (raw-logit CE is bf16-noise-sensitive; see zoo.py), so
    # the output dtype is float32.
    assert out.dtype == jnp.float32 and out.shape == (2, 10)

    import dataclasses

    from dopt.config import (DataConfig, ExperimentConfig, GossipConfig,
                             ModelConfig, OptimizerConfig)
    from dopt.engine import GossipTrainer

    cfg = ExperimentConfig(
        name="bf16", seed=5,
        data=DataConfig(dataset="synthetic", num_users=4,
                        synthetic_train_size=512, synthetic_test_size=128),
        model=ModelConfig(model="mlp", input_shape=(28, 28, 1),
                          faithful=False, compute_dtype="bfloat16"),
        optim=OptimizerConfig(lr=0.1, momentum=0.5),
        gossip=GossipConfig(algorithm="dsgd", topology="circle",
                            mode="metropolis", rounds=4, local_ep=1,
                            local_bs=32),
    )
    tr = GossipTrainer(cfg)
    h = tr.run(rounds=4, block=2)
    accs = [r["avg_test_acc"] for r in h.rows if "avg_test_acc" in r]
    assert accs[-1] > 0.6, accs


def test_max_pool_first_winner_tie_gradients_match_torch():
    """The reshape-max pool's custom VJP must route tie gradients to the
    FIRST window element in kernel scan order, exactly like torch's
    MaxPool2d backward — ties are common on real data (zero-background
    MNIST under the faithful no-ReLU conv gives exact 4-way bias ties
    in every background window, ADVICE r4)."""
    torch = pytest.importorskip("torch")

    from dopt.models.zoo import _max_pool_2x2

    rng = np.random.default_rng(0)
    # Quantised values force plenty of exact ties, including all-equal
    # windows; a zero block models MNIST background.
    x = rng.integers(-2, 3, size=(2, 8, 8, 3)).astype(np.float32)
    x[0, :4, :4, :] = 0.0
    # Weighted sum output so the upstream gradient is non-uniform.
    gw = rng.normal(size=(2, 4, 4, 3)).astype(np.float32)

    gj = jax.grad(
        lambda a: jnp.sum(_max_pool_2x2(a) * gw))(jnp.asarray(x))

    xt = torch.tensor(np.moveaxis(x, -1, 1), requires_grad=True)  # NCHW
    out = torch.nn.functional.max_pool2d(xt, 2, 2)
    out.backward(torch.tensor(np.moveaxis(gw, -1, 1)))
    gt = np.moveaxis(xt.grad.numpy(), 1, -1)

    np.testing.assert_array_equal(np.asarray(gj), gt)


@jax.custom_vjp
def _cascade_tiled_max(x6):
    """The oracle of the pool tests: the formulation ``_tiled_max`` had
    before PR 26 (residual ``(x6, m)``, a boolean first-winner cascade
    over the four window slices, two levels of ``stack``)."""
    return x6.max(axis=(2, 4))


def _cascade_fwd(x6):
    m = x6.max(axis=(2, 4))
    return m, (x6, m)


def _cascade_bwd(res, g):
    x6, m = res
    e = [x6[:, :, i, :, j, :] == m for i in (0, 1) for j in (0, 1)]
    seen = e[0]
    masks = [e[0]]
    for k in (1, 2, 3):
        masks.append(e[k] & ~seen)
        seen = seen | e[k]
    gm = [g * mk.astype(g.dtype) for mk in masks]
    return (jnp.stack([jnp.stack([gm[0], gm[1]], axis=3),
                       jnp.stack([gm[2], gm[3]], axis=3)], axis=2),)


_cascade_tiled_max.defvjp(_cascade_fwd, _cascade_bwd)


def _pool_case(name):
    rng = np.random.default_rng(7)
    if name == "quantised_ties":
        return rng.integers(-2, 3, size=(3, 8, 12, 5)) * 0.5
    if name == "all_equal_block":
        x = rng.normal(size=(2, 8, 8, 4))
        x[0] = 1.25
        x[1, 2:6, 2:6] = -3.0
        return x
    if name == "mnist_zero_background":
        # a bias on a zero background: exact 4-way ties in every
        # background window, a few strokes with distinct values
        x = np.zeros((4, 28, 28, 6))
        x[:, 9:19, 12:16] = rng.normal(size=(4, 10, 4, 6))
        return x + rng.normal(size=(6,))
    if name == "stacked_4c":
        return rng.integers(-3, 4, size=(5, 6, 6, 4 * 8)) * 0.25
    raise KeyError(name)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", ["quantised_ties", "all_equal_block",
                                  "mnist_zero_background", "stacked_4c"])
def test_max_pool_bit_equal_to_cascade_oracle(case, dtype):
    """Forward and gradient of the one-pass pool (int8 winner code as
    the only residual) equal the old cascade's bit for bit, ties and
    all-equal windows included."""
    from dopt.models.zoo import _max_pool_2x2

    x = jnp.asarray(_pool_case(case), dtype)
    b, h, w, c = x.shape
    gw = jnp.asarray(np.random.default_rng(8).normal(
        size=(b, h // 2, w // 2, c)), dtype)

    def oracle(a):
        return _cascade_tiled_max(a.reshape(b, h // 2, 2, w // 2, 2, c))

    got, got_vjp = jax.vjp(_max_pool_2x2, x)
    want, want_vjp = jax.vjp(oracle, x)
    assert got.dtype == want.dtype == x.dtype
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    (gg,), (gw_,) = got_vjp(gw), want_vjp(gw)
    assert gg.dtype == gw_.dtype == x.dtype
    np.testing.assert_array_equal(np.asarray(gg, np.float32),
                                  np.asarray(gw_, np.float32))
    # the undifferentiated call is the same function
    np.testing.assert_array_equal(
        np.asarray(_max_pool_2x2(x), np.float32),
        np.asarray(want, np.float32))


def test_max_pool_odd_dims_fall_back_to_flax():
    """Odd H or W cannot be tiled: the pool is ``nn.max_pool`` (which
    floors), forward and gradient."""
    import flax.linen as nn

    from dopt.models.zoo import _max_pool_2x2

    x = jnp.asarray(np.random.default_rng(3).normal(size=(2, 7, 6, 3)),
                    jnp.float32)
    ref = lambda a: nn.max_pool(a, (2, 2), strides=(2, 2))
    assert _max_pool_2x2(x).shape == (2, 3, 3, 3)
    np.testing.assert_array_equal(np.asarray(_max_pool_2x2(x)),
                                  np.asarray(ref(x)))
    np.testing.assert_array_equal(
        np.asarray(jax.grad(lambda a: jnp.sum(_max_pool_2x2(a) ** 2))(x)),
        np.asarray(jax.grad(lambda a: jnp.sum(ref(a) ** 2))(x)))


def test_stacked_model1_vjp_saves_winner_codes_not_conv_outputs(capsys):
    """What the stacked Model1 step keeps between forward and backward:
    for each pool an int8 code of the POOLED size, and no array of a
    conv output's size (the convs need their inputs, never their
    outputs: the faithful stack has no activation behind them)."""
    import re

    from jax.ad_checkpoint import print_saved_residuals

    from dopt.models import make_stacked_apply

    m = build_model("model1")
    w, b = 3, 4
    p1 = _init(m, (28, 28, 1))
    stacked = jax.tree.map(lambda a: jnp.stack([a] * w), p1)
    x = jnp.zeros((w, b, 28, 28, 1), jnp.float32)
    apply = make_stacked_apply(m)
    print_saved_residuals(lambda p: apply(p, x), stacked)
    res = [(dt, tuple(int(d) for d in dims.split(",") if d))
           for dt, dims in re.findall(r"^(\w+)\[([\d,]*)\]",
                                      capsys.readouterr().out, re.M)]
    assert len(res) > 4, res
    conv_out = {b * 28 * 28 * w * 32, b * 14 * 14 * w * 64}
    too_big = [r for r in res if int(np.prod(r[1])) in conv_out]
    assert not too_big, too_big
    codes = sorted(shape for dt, shape in res if dt == "i8")
    assert codes == [(b, 7, 7, w * 64), (b, 14, 14, w * 32)], codes


@pytest.mark.parametrize("w, b", [(2, 3), (4, 128)],
                         ids=["grouped-w2", "packed-w4"])
def test_stacked_cnn_apply_non_square_input(w, b):
    """The grouped-stacked CNN forward must handle non-square inputs
    (fc1's VALID-conv kernel reshape derives H'/W' from the activation
    shape, not a square-root guess — ADVICE r4), with conv1 in its
    grouped form and packed four workers a group."""
    from dopt.models import make_stacked_apply

    m = build_model("model1", faithful=False)
    shape = (12, 8, 1)
    p1 = _init(m, shape)
    stacked = jax.tree.map(lambda a: jnp.stack([a] * w), p1)
    x = jnp.asarray(np.random.default_rng(1).normal(
        size=(w, b, *shape)), jnp.float32)
    out = make_stacked_apply(m)(stacked, x)
    assert out.shape == (w, b, 10)
    for i in (0, w - 1):
        ref = m.apply({"params": p1}, x[i])
        np.testing.assert_allclose(np.asarray(out[i]), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)


# ---- conv1 of the stacked reference CNNs: four workers a group (PR 31)
#      where the fleet packs evenly AND the batch is whole lane tiles
#      (B % 128 == 0) or the fleet is 160 wide; small images keep it cheap
def _stacked_fleet(name, w, b=128, shape=(8, 8, 1), seed=0, **model_kw):
    """(model, [W, ...] params of W differently initialised workers,
    [W, B, ...] inputs, [W, B, classes] cotangent)."""
    m = build_model(name, **model_kw)
    keys = jax.random.split(jax.random.PRNGKey(seed), w)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (w, b, *shape))
    params = jax.vmap(lambda k: m.init(k, x[0])["params"])(keys)
    cot = jax.random.normal(jax.random.PRNGKey(seed + 2), (w, b, 10))
    return m, params, x, cot


def _grouped_conv1(monkeypatch):
    """conv1 as the parent of PR 31 called it: one group a worker."""
    from dopt.models import zoo

    monkeypatch.setattr(
        zoo, "_conv1_stacked",
        lambda z, k, groups, *, dtype, bias: zoo._conv_fast(
            z, k, groups, dtype=dtype, bias=bias))


def _out_and_grads(apply, params, x, cot):
    """Outputs, and the gradient leaves of ``sum(outputs · cot)``."""
    return apply(params, x), jax.grad(
        lambda p: jnp.sum(apply(p, x) * cot))(params)


@pytest.mark.parametrize("w, b", [(4, 128), (8, 128), (12, 256), (160, 5)],
                         ids=["w4-b128", "w8-b128", "w12-b256", "w160-b5"])
def test_packed_conv1_equals_the_grouped_form(w, b, monkeypatch):
    """Outputs and every gradient leaf of the stacked Model1 apply, conv1
    packed four workers a group against one group a worker: the same
    25-tap sums plus exact zeros, so float32 reassociation only."""
    from dopt.models import make_stacked_apply

    m, params, x, cot = _stacked_fleet("model1", w, b=b)
    with jax.default_matmul_precision("highest"):
        text = jax.jit(make_stacked_apply(m)).lower(params, x).as_text()
        assert f"feature_group_count = {w // 4} " in text
        out, grads = _out_and_grads(make_stacked_apply(m), params, x, cot)
        _grouped_conv1(monkeypatch)
        ref, ref_grads = _out_and_grads(make_stacked_apply(m), params, x,
                                        cot)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6,
                               atol=1e-7)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, g), r in zip(flat, jax.tree.leaves(ref_grads)):
        scale = float(jnp.abs(r).max())
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(r), rtol=1e-6, atol=1e-6 * scale,
            err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("name, w, b, shape", [
    ("model1", 1, 128, (8, 8, 1)), ("model1", 6, 128, (8, 8, 1)),
    ("model3", 4, 128, (8, 8, 3)), ("model1", 8, 50, (8, 8, 1)),
    ("model1", 128, 50, (8, 8, 1)),
], ids=["model1-w1", "model1-w6", "model3-w4", "model1-w8-b50",
        "model1-w128-b50"])
def test_conv1_keeps_the_grouped_program(name, w, b, shape, monkeypatch):
    """The single global model, a fleet that does not pack evenly, a
    3-channel input, and a narrow fleet whose batch is not whole lane
    tiles (FedAvg's 8 lanes of 50 rows: packing loses there on the chip)
    lower to the parent's program, text for text."""
    from dopt.models import make_stacked_apply

    m, params, x, _ = _stacked_fleet(name, w, b=b, shape=shape)
    text = jax.jit(make_stacked_apply(m)).lower(params, x).as_text()
    _grouped_conv1(monkeypatch)
    assert text == jax.jit(make_stacked_apply(m)).lower(params, x).as_text()
    assert text.count(f"feature_group_count = {w} ") == 4


@pytest.mark.parametrize("ambient, kernel", [
    (None, "DEFAULT"), ("bfloat16", "DEFAULT"), ("highest", "HIGHEST"),
], ids=["ambient-unset", "ambient-bfloat16", "ambient-highest"])
def test_packed_conv1_keeps_the_pixels_float32(ambient, kernel):
    """The grouped form ran on the vector unit with float32 pixels against
    a kernel (forward) or cotangent (weight gradient) at the ambient
    precision; the packed form asks the MXU for the same operands.  conv2,
    fc1 and fc2 take the ambient precision on both operands, as ever."""
    from dopt.models import make_stacked_apply

    m, params, x, cot = _stacked_fleet("model1", 8, b=128)
    apply = make_stacked_apply(m)
    with jax.default_matmul_precision(ambient):
        text = jax.jit(jax.grad(
            lambda p: jnp.sum(apply(p, x) * cot))).lower(params).as_text()
    conv1 = [line for line in text.splitlines()
             if "stablehlo.convolution" in line
             and ("feature_group_count = 2 " in line       # forward
                  or "batch_group_count = 2 " in line)]    # weight gradient
    assert len(conv1) == 2
    for line in conv1:
        assert (f"precision_config = [#stablehlo<precision HIGHEST>, "
                f"#stablehlo<precision {kernel}>]") in line
    rest = [line for line in text.splitlines()
            if "stablehlo.convolution" in line and line not in conv1]
    assert rest and not any("precision HIGHEST>, #stablehlo<precision DEFAULT"
                            in line for line in rest)


@pytest.mark.parametrize("poison", [float("nan"), float("inf")],
                         ids=["nan", "inf"])
def test_packed_conv1_isolates_a_nonfinite_worker(poison):
    """Worker 1's conv1 kernel gone non-finite changes no bit of its
    pack-mates' (0, 2, 3) outputs or gradients, nor of the next pack's:
    the zero blocks are selected, never multiplied in.  (The corrected
    head keeps every cotangent finite.  A non-finite COTANGENT is another
    matter on this backend: XLA:CPU's weight gradient of any grouped
    convolution spreads it over all groups, in the parent's form too.)"""
    from dopt.models import make_stacked_apply

    w = 8
    m, params, x, cot = _stacked_fleet("model1", w, faithful=False)
    apply = make_stacked_apply(m)
    assert "feature_group_count = 2 " in jax.jit(apply).lower(
        params, x).as_text()
    conv1 = params["conv1"]
    bad = {**params, "conv1": {
        **conv1, "kernel": conv1["kernel"].at[1].set(poison)}}
    out, grads = _out_and_grads(apply, params, x, cot)
    bad_out, bad_grads = _out_and_grads(apply, bad, x, cot)
    others = np.array([0, 2, 3, 4, 5, 6, 7])
    assert np.isfinite(np.asarray(out)).all()
    assert not np.isfinite(np.asarray(bad_out[1])).any()
    np.testing.assert_array_equal(np.asarray(bad_out)[others],
                                  np.asarray(out)[others])
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, g), bg in zip(flat, jax.tree.leaves(bad_grads)):
        assert np.isfinite(np.asarray(g)).all()
        np.testing.assert_array_equal(
            np.asarray(bg)[others], np.asarray(g)[others],
            err_msg=jax.tree_util.keystr(path))
    assert not np.isfinite(np.asarray(bad_grads["conv2"]["kernel"][1])).all()


def test_packed_kernel_gradient_keeps_the_diagonal_blocks():
    """The selection's transpose: a cotangent that is NaN everywhere off
    the diagonal blocks (what a diverged pack-mate's output gradient
    leaves there) gives each worker its own block and nothing else."""
    from dopt.models.zoo import _pack_kernel

    w, c_out, p = 8, 32, 4
    g_kernel = jax.random.normal(jax.random.PRNGKey(0), (5, 5, 1, w * c_out))
    packed, vjp = jax.vjp(lambda k: _pack_kernel(k, c_out, p), g_kernel)
    own = (np.arange(w * c_out) // c_out) % p           # [W·C_out]
    diag = np.arange(p)[:, None] == own[None, :]        # [p, W·C_out]
    np.testing.assert_array_equal(
        np.asarray(packed), np.where(diag, np.asarray(g_kernel), 0.0))
    ct = jnp.where(diag, jnp.arange(1.0, w * c_out + 1), jnp.nan)
    (grad,) = vjp(jnp.broadcast_to(ct, packed.shape))
    np.testing.assert_array_equal(
        np.asarray(grad),
        np.broadcast_to(np.arange(1.0, w * c_out + 1, dtype=np.float32),
                        g_kernel.shape))


def test_resnet_stage_sizes_override():
    """stage_sizes builds shallow ResNet variants (dryrun/test trims) and
    is rejected for non-resnet models."""
    m = build_model("resnet18", stage_sizes=(1, 1))
    p = _init(m, (8, 8, 1))
    blocks = [k for k in p if k.startswith("ResidualBlock")]
    assert len(blocks) == 2, blocks
    out = m.apply({"params": p}, jnp.zeros((2, 8, 8, 1)))
    assert out.shape == (2, 10)
    with pytest.raises(ValueError, match="resnet18 only"):
        build_model("mlp", stage_sizes=(1, 1))
