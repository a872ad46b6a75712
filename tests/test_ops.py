"""Pallas fused-update kernel: exact parity with the jnp SGD path.

Runs in interpret mode on the CPU test mesh — the identical kernel code
compiles on TPU.  SURVEY §4 layer-1: algorithm steps as pure functions.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dopt.ops import fused_sgd_momentum, fused_sgd_momentum_tree
from dopt.optim import SGDState, sgd_step


@pytest.mark.parametrize("shape", [(7,), (128,), (513,), (32, 33), (4, 100, 17)])
def test_fused_matches_sgd_step_exact(shape, devices):
    rng = np.random.default_rng(0)
    p = jnp.asarray(rng.normal(size=shape).astype(np.float32))
    m = jnp.asarray(rng.normal(size=shape).astype(np.float32))
    g = jnp.asarray(rng.normal(size=shape).astype(np.float32))
    want_p, want_st = sgd_step(p, SGDState(m), g, lr=0.1, momentum=0.5)
    got_p, got_m = fused_sgd_momentum(p, m, g, lr=0.1, mu=0.5, interpret=True)
    # Same fp32 ops; only fused-multiply-add association may differ.
    np.testing.assert_allclose(np.asarray(got_p), np.asarray(want_p),
                               rtol=1e-6, atol=1e-7)
    np.testing.assert_array_equal(np.asarray(got_m), np.asarray(want_st.momentum))


def test_fused_tree_under_vmap_scan(devices):
    # The kernel must survive the engine's composition: vmap over the
    # worker axis, scan over steps, jit outside.
    rng = np.random.default_rng(1)
    W, S, D = 4, 3, 300
    tree = {
        "a": jnp.asarray(rng.normal(size=(W, D)).astype(np.float32)),
        "b": jnp.asarray(rng.normal(size=(W, 5, 7)).astype(np.float32)),
    }
    mom = jax.tree.map(jnp.zeros_like, tree)
    gs = {
        "a": jnp.asarray(rng.normal(size=(S, W, D)).astype(np.float32)),
        "b": jnp.asarray(rng.normal(size=(S, W, 5, 7)).astype(np.float32)),
    }

    def one_worker(p, m, g_steps):
        def step(carry, g):
            p, m = carry
            p, m = fused_sgd_momentum_tree(p, m, g, lr=0.05, mu=0.9,
                                           interpret=True)
            return (p, m), None

        (p, m), _ = jax.lax.scan(step, (p, m), g_steps)
        return p, m

    @jax.jit
    def run(tree, mom, gs):
        gs_w = jax.tree.map(lambda x: jnp.moveaxis(x, 0, 1), gs)  # [W,S,...]
        return jax.vmap(one_worker)(tree, mom, gs_w)

    got_p, got_m = run(tree, mom, gs)

    # Reference: plain sgd_step in the same composition.
    def one_worker_ref(p, m, g_steps):
        def step(carry, g):
            p, m = carry
            p, st = sgd_step(p, SGDState(m), g, lr=0.05, momentum=0.9)
            return (p, st.momentum), None

        (p, m), _ = jax.lax.scan(step, (p, m), g_steps)
        return p, m

    @jax.jit
    def run_ref(tree, mom, gs):
        gs_w = jax.tree.map(lambda x: jnp.moveaxis(x, 0, 1), gs)
        return jax.vmap(one_worker_ref)(tree, mom, gs_w)

    want_p, want_m = run_ref(tree, mom, gs)
    for k in tree:
        np.testing.assert_allclose(np.asarray(got_p[k]), np.asarray(want_p[k]),
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(np.asarray(got_m[k]), np.asarray(want_m[k]),
                                   rtol=1e-6, atol=1e-6)


def test_engine_with_fused_update(devices):
    # End-to-end: GossipTrainer with fused_update=True learns and matches
    # the jnp-update run exactly (interpret mode on CPU).
    import dataclasses

    from dopt.config import (DataConfig, ExperimentConfig, GossipConfig,
                             ModelConfig, OptimizerConfig)
    from dopt.engine import GossipTrainer

    def mk(fused):
        return ExperimentConfig(
            name="t", seed=9,
            data=DataConfig(dataset="synthetic", num_users=4,
                            synthetic_train_size=256, synthetic_test_size=64),
            model=ModelConfig(model="mlp", input_shape=(28, 28, 1),
                              faithful=False),
            optim=OptimizerConfig(lr=0.1, momentum=0.5, fused_update=fused),
            gossip=GossipConfig(algorithm="dsgd", topology="circle",
                                mode="metropolis", rounds=2, local_ep=1,
                                local_bs=32),
        )

    a = GossipTrainer(mk(False)); a.run(rounds=2)
    b = GossipTrainer(mk(True)); b.run(rounds=2)
    fa = np.concatenate([np.ravel(x) for x in jax.tree.leaves(jax.device_get(a.params))])
    fb = np.concatenate([np.ravel(x) for x in jax.tree.leaves(jax.device_get(b.params))])
    np.testing.assert_allclose(fa, fb, rtol=1e-6, atol=1e-7)


# ---------------------------------------------------------------------------
# Fused mix + update (the gossip epilogue, ROADMAP raw-speed lever 3)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,f", [(6, 137), (5, 1000), (8, 128), (3, 1)])
def test_fused_mix_sgd_matches_reference(n, f, devices):
    # One HBM pass of W @ p − lr·buf on a flat bucket must agree with
    # the jnp composition (f32 matrix + accumulation — the scatter-path
    # numerics contract) to reassociation tolerance.
    from dopt.ops import fused_mix_sgd

    rng = np.random.default_rng(3)
    p = jnp.asarray(rng.normal(size=(n, f)).astype(np.float32))
    m = jnp.asarray(rng.normal(size=(n, f)).astype(np.float32))
    w = jnp.asarray(rng.dirichlet(np.ones(n), size=n).astype(np.float32))
    got = fused_mix_sgd(p, m, w, lr=0.05, interpret=True)
    want = (jnp.tensordot(w, p, axes=[[1], [0]]) - 0.05 * m)
    assert got.shape == p.shape and got.dtype == p.dtype
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_fused_mix_sgd_bf16_storage(devices):
    # bf16 leaf storage: matrix + accumulation stay f32, only the final
    # store rounds — same contract as mix_dense_scatter.
    from dopt.ops import fused_mix_sgd

    rng = np.random.default_rng(4)
    p32 = rng.normal(size=(4, 300)).astype(np.float32)
    m32 = rng.normal(size=(4, 300)).astype(np.float32)
    p = jnp.asarray(p32).astype(jnp.bfloat16)
    m = jnp.asarray(m32).astype(jnp.bfloat16)
    w = jnp.asarray(rng.dirichlet(np.ones(4), size=4).astype(np.float32))
    got = fused_mix_sgd(p, m, w, lr=0.1, interpret=True)
    assert got.dtype == jnp.bfloat16
    want = (jnp.tensordot(w, p.astype(jnp.float32), axes=[[1], [0]])
            - 0.1 * m.astype(jnp.float32)).astype(jnp.bfloat16)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_fused_mix_update_tree_over_buckets(devices):
    # The engine-facing wrapper rides the UpdateShardSpec flat-bucket
    # layout: multi-bucket round trip, identical to the tree-level jnp
    # reference.
    from dopt.ops import fused_mix_update, mix_sgd_reference
    from dopt.parallel.collectives import make_update_shard_spec

    rng = np.random.default_rng(5)
    tree = {"a": jnp.asarray(rng.normal(size=(6, 33)).astype(np.float32)),
            "b": jnp.asarray(rng.normal(size=(6, 5, 7)).astype(np.float32))}
    mom = jax.tree.map(
        lambda x: jnp.asarray(
            rng.normal(size=x.shape).astype(np.float32)), tree)
    spec = make_update_shard_spec(tree, fold=2, bucket_bytes=64)
    assert spec.num_buckets > 1  # exercise the per-bucket loop
    w = rng.dirichlet(np.ones(6), size=6).astype(np.float32)
    got = fused_mix_update(tree, mom, w, spec, lr=0.1, interpret=True)
    want = mix_sgd_reference(tree, mom, w, lr=0.1)
    assert jax.tree.structure(got) == jax.tree.structure(tree)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)


def test_pallas_mode_is_chosen_by_backend_never_silently(monkeypatch):
    """Compiled on tpu, interpreted on cpu (these tests), an error on
    anything else — no backend gets a quiet interpreter."""
    import pytest

    from dopt.ops import fused_update

    assert fused_update.pallas_interpret() is True          # the CPU mesh
    monkeypatch.setattr(fused_update.jax, "default_backend", lambda: "tpu")
    assert fused_update.pallas_interpret() is False
    monkeypatch.setattr(fused_update.jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu'"):
        fused_update.pallas_interpret()
