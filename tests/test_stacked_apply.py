"""Grouped stacked-forward fast path vs the vmapped per-worker path.

``dopt.models.make_stacked_apply`` reorganises the reference CNNs'
stacked-fleet forward into one feature-grouped conv program (worker
axis in the channel dimension).  The math is identical to
``vmap(model.apply)`` up to float reassociation inside the conv, so
every surface the engines consume — forward, one-step update, the
epoch-structured update with local-val eval, and the evaluators — must
agree within float tolerance, for both reference CNNs and both head
modes.  The engine-level test pins that stacked_impl='auto' and 'vmap'
produce the same training trajectory.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dopt.engine.local import (make_stacked_evaluator,
                               make_stacked_local_update,
                               make_stacked_local_update_epochs,
                               make_stacked_local_update_gather)
from dopt.models import build_model, make_stacked_apply

W, B, S = 3, 8, 4


def _setup(model_name, faithful):
    shape = (28, 28, 1) if model_name == "model1" else (32, 32, 3)
    model = build_model(model_name, faithful=faithful)
    p0 = model.init(jax.random.key(0), jnp.zeros((1, *shape)))["params"]
    rng = np.random.default_rng(7)
    stacked = jax.tree.map(
        lambda x: jnp.asarray(np.stack([
            np.asarray(x) + 0.01 * i for i in range(W)])), p0)
    x = jnp.asarray(rng.normal(size=(W, B, *shape)).astype(np.float32))
    y = jnp.asarray(rng.integers(0, 10, (W, B)).astype(np.int32))
    return model, stacked, x, y


@pytest.mark.parametrize("model_name", ["model1", "model3"])
@pytest.mark.parametrize("faithful", [True, False])
def test_forward_parity(model_name, faithful):
    model, stacked, x, y = _setup(model_name, faithful)
    s_apply = make_stacked_apply(model)
    assert s_apply is not None
    got = jax.jit(s_apply)(stacked, x)
    want = jax.jit(jax.vmap(
        lambda p, xx: model.apply({"params": p}, xx)))(stacked, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


def test_unsupported_models_return_none():
    for name in ("mlp", "logistic"):
        assert make_stacked_apply(build_model(name)) is None


def test_resnet_forward_parity():
    """Grouped-stacked ResNet-18 (the north-star model) vs vmap."""
    model = build_model("resnet18", faithful=False)
    p0 = model.init(jax.random.key(1), jnp.zeros((1, 32, 32, 3)))["params"]
    rng = np.random.default_rng(11)
    stacked = jax.tree.map(
        lambda v: jnp.asarray(np.stack([
            np.asarray(v) * (1 + 0.05 * i) for i in range(W)])), p0)
    x = jnp.asarray(rng.normal(size=(W, 4, 32, 32, 3)).astype(np.float32))
    s_apply = make_stacked_apply(model)
    assert s_apply is not None
    got = jax.jit(s_apply)(stacked, x)
    want = jax.jit(jax.vmap(
        lambda p, xx: model.apply({"params": p}, xx)))(stacked, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=5e-4, atol=5e-5)


def test_resnet_update_parity():
    """One SGD step through the grouped-stacked ResNet matches vmap."""
    model = build_model("resnet18", faithful=False)
    p0 = model.init(jax.random.key(1), jnp.zeros((1, 32, 32, 3)))["params"]
    rng = np.random.default_rng(12)
    stacked = jax.tree.map(
        lambda v: jnp.asarray(np.stack([np.asarray(v)] * W)), p0)
    mom = jax.tree.map(jnp.zeros_like, stacked)
    bx = jnp.asarray(rng.normal(size=(W, 2, 4, 32, 32, 3)).astype(np.float32))
    by = jnp.asarray(rng.integers(0, 10, (W, 2, 4)).astype(np.int32))
    bw = jnp.ones((W, 2, 4), jnp.float32)
    s_apply = make_stacked_apply(model)
    kw = dict(lr=0.05, momentum=0.9)
    f_v = make_stacked_local_update(model.apply, **kw)
    f_s = make_stacked_local_update(model.apply, **kw, stacked_apply=s_apply)
    pv, mv, lv, av = jax.jit(f_v)(stacked, mom, bx, by, bw)
    ps, ms, ls, as_ = jax.jit(f_s)(stacked, mom, bx, by, bw)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-4), pv, ps)
    np.testing.assert_allclose(np.asarray(lv), np.asarray(ls),
                               rtol=1e-3, atol=1e-4)


@pytest.mark.parametrize("algorithm", ["sgd", "fedprox", "fedadmm",
                                       "scaffold"])
def test_local_update_parity(algorithm):
    model, stacked, x, y = _setup("model1", True)
    s_apply = make_stacked_apply(model)
    mom = jax.tree.map(jnp.zeros_like, stacked)
    bx = jnp.stack([x] * S, axis=1)          # [W, S, B, ...]
    by = jnp.stack([y] * S, axis=1)
    bw = jnp.ones((W, S, B), jnp.float32)
    theta = jax.tree.map(lambda v: v[0], stacked)
    # fedadmm: worker-stacked duals; scaffold: theta slot = server
    # control c (broadcast, NONZERO so a slot swap cannot cancel),
    # alpha slot = client controls c_i (stacked).
    alpha = jax.tree.map(
        lambda v: 0.01 * jnp.ones_like(v) * (1 + jnp.arange(W).reshape(
            (W,) + (1,) * (v.ndim - 1))), stacked)
    kw = dict(lr=0.05, momentum=0.5, algorithm=algorithm, rho=0.1)
    args = {"sgd": (stacked, mom, bx, by, bw),
            "fedprox": (stacked, mom, bx, by, bw, theta),
            "fedadmm": (stacked, mom, bx, by, bw, theta, alpha),
            "scaffold": (stacked, mom, bx, by, bw, theta, alpha)}[algorithm]
    f_v = make_stacked_local_update(model.apply, **kw)
    f_s = make_stacked_local_update(model.apply, **kw, stacked_apply=s_apply)
    pv, mv, lv, av = jax.jit(f_v)(*args)
    ps, ms, ls, as_ = jax.jit(f_s)(*args)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5), pv, ps)
    np.testing.assert_allclose(np.asarray(lv), np.asarray(ls),
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(av), np.asarray(as_), atol=1e-6)


def test_gather_and_epochs_parity():
    model, stacked, x, y = _setup("model1", True)
    s_apply = make_stacked_apply(model)
    mom = jax.tree.map(jnp.zeros_like, stacked)
    rng = np.random.default_rng(3)
    n = 64
    tx = jnp.asarray(rng.normal(size=(n, 28, 28, 1)).astype(np.float32))
    ty = jnp.asarray(rng.integers(0, 10, n).astype(np.int32))
    idx = jnp.asarray(rng.integers(0, n, (W, S, B)).astype(np.int32))
    bw = jnp.ones((W, S, B), jnp.float32)
    kw = dict(lr=0.05, momentum=0.5)
    for chunks in (None, 2):
        f_v = make_stacked_local_update_gather(model.apply, **kw,
                                               gather_chunks=chunks)
        f_s = make_stacked_local_update_gather(model.apply, **kw,
                                               gather_chunks=chunks,
                                               stacked_apply=s_apply)
        pv, mv, lv, av = jax.jit(f_v)(stacked, mom, idx, bw, tx, ty)
        ps, ms, ls, as_ = jax.jit(f_s)(stacked, mom, idx, bw, tx, ty)
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5), pv, ps)
        np.testing.assert_allclose(np.asarray(lv), np.asarray(ls),
                                   rtol=2e-4, atol=2e-5)

    # Epoch-structured variant with per-epoch local-val eval.
    e = 2
    idx_e = idx.reshape(W, e, S // e, B)
    bw_e = bw.reshape(idx_e.shape)
    vi = jnp.asarray(rng.integers(0, n, (W, 2, B)).astype(np.int32))
    vw = jnp.ones((W, 2, B), jnp.float32)
    f_v = make_stacked_local_update_epochs(model.apply, **kw)
    f_s = make_stacked_local_update_epochs(model.apply, **kw,
                                           stacked_apply=s_apply)
    pv, mv, emv = jax.jit(f_v)(stacked, mom, idx_e, bw_e, tx, ty, vi, vw)
    ps, ms, ems = jax.jit(f_s)(stacked, mom, idx_e, bw_e, tx, ty, vi, vw)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5), pv, ps)
    assert set(emv) == set(ems)
    for k in emv:
        np.testing.assert_allclose(np.asarray(emv[k]), np.asarray(ems[k]),
                                   rtol=5e-4, atol=5e-5)


def test_evaluator_parity():
    model, stacked, x, y = _setup("model1", True)
    s_apply = make_stacked_apply(model)
    ex = jnp.stack([x[0]] * 2)               # [S=2, B, ...] shared stack
    ey = jnp.stack([y[0]] * 2)
    ew = jnp.ones((2, B), jnp.float32)
    ev_v = make_stacked_evaluator(model.apply)
    ev_s = make_stacked_evaluator(model.apply, stacked_apply=s_apply)
    mv = jax.jit(ev_v)(stacked, ex, ey, ew)
    ms = jax.jit(ev_s)(stacked, ex, ey, ew)
    for k in ("acc", "loss_sum", "loss_mean", "count"):
        np.testing.assert_allclose(np.asarray(mv[k]), np.asarray(ms[k]),
                                   rtol=2e-4, atol=2e-5)


def test_engine_trajectory_parity():
    """GossipTrainer with stacked_impl='auto' vs 'vmap': same history."""
    from dopt.config import (DataConfig, ExperimentConfig, GossipConfig,
                             ModelConfig, OptimizerConfig)
    from dopt.engine import GossipTrainer

    def run(impl):
        cfg = ExperimentConfig(
            name=f"stacked-{impl}", seed=5,
            data=DataConfig(dataset="synthetic", num_users=4, iid=False,
                            shards=2, synthetic_train_size=96,
                            synthetic_test_size=32),
            model=ModelConfig(model="model1", faithful=True,
                              stacked_impl=impl),
            optim=OptimizerConfig(lr=0.05, momentum=0.5),
            gossip=GossipConfig(algorithm="dsgd", topology="circle",
                                mode="stochastic", rounds=2, local_ep=1,
                                local_bs=8),
        )
        tr = GossipTrainer(cfg)
        h = tr.run(rounds=2)
        return h.rows

    rows_a, rows_v = run("auto"), run("vmap")
    assert len(rows_a) == len(rows_v)
    for ra, rv in zip(rows_a, rows_v):
        for k in ra:
            if isinstance(ra[k], float):
                assert abs(ra[k] - rv[k]) < 5e-4, (k, ra[k], rv[k])
