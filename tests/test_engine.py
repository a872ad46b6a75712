"""End-to-end engine tests on the 8-device virtual CPU mesh.

These are the SURVEY §4 layer-3 tests: multi-worker semantics without a
cluster, on synthetic learnable data so accuracy movement is meaningful.
"""

import dataclasses

import numpy as np
import pytest

from dopt.config import DataConfig, ExperimentConfig, FederatedConfig, GossipConfig, ModelConfig, OptimizerConfig
from dopt.engine import FederatedTrainer, GossipTrainer


def _gossip_cfg(**kw):
    g = dict(algorithm="dsgd", topology="circle", mode="metropolis",
             rounds=3, local_ep=1, local_bs=32)
    g.update(kw.pop("gossip", {}))
    return ExperimentConfig(
        name="t",
        seed=7,
        data=DataConfig(dataset="synthetic", num_users=kw.pop("num_users", 8),
                        iid=kw.pop("iid", True), shards=2,
                        synthetic_train_size=512, synthetic_test_size=128,
                        **kw.pop("data_extra", {})),
        model=ModelConfig(model="mlp", input_shape=(28, 28, 1),
                          faithful=False),
        optim=OptimizerConfig(lr=0.1, momentum=0.5),
        gossip=GossipConfig(**g),
        **kw,
    )


def _fed_cfg(algorithm="fedavg", **kw):
    return ExperimentConfig(
        name="t",
        seed=7,
        data=DataConfig(dataset="synthetic", num_users=kw.pop("num_users", 8),
                        iid=True, synthetic_train_size=512,
                        synthetic_test_size=128),
        model=ModelConfig(model="mlp", input_shape=(28, 28, 1),
                          faithful=False),
        optim=OptimizerConfig(lr=0.1, momentum=0.5, rho=0.1),
        federated=FederatedConfig(algorithm=algorithm, frac=0.5, rounds=3,
                                  local_ep=1, local_bs=32),
        **kw,
    )


def test_dsgd_learns(devices):
    tr = GossipTrainer(_gossip_cfg())
    h = tr.run(rounds=4)
    accs = [r["avg_test_acc"] for r in h if "avg_test_acc" in r]
    assert accs[-1] > 0.6, accs
    assert accs[-1] > accs[0]


def test_dsgd_consensus_shrinks_disagreement(devices):
    # After many rounds of doubly-stochastic mixing, workers' params
    # should be closer together than under no consensus.
    import jax
    cfg = _gossip_cfg(iid=False)
    tr = GossipTrainer(cfg)
    tr.run(rounds=4)
    leaves = jax.tree.leaves(tr.params)
    spread_dsgd = max(float(np.std(np.asarray(l), axis=0).max()) for l in leaves)

    cfg2 = _gossip_cfg(iid=False, gossip={"algorithm": "nocons"})
    tr2 = GossipTrainer(cfg2)
    tr2.run(rounds=4)
    leaves2 = jax.tree.leaves(tr2.params)
    spread_nocons = max(float(np.std(np.asarray(l), axis=0).max()) for l in leaves2)
    assert spread_dsgd < spread_nocons


def test_nocons_noniid_worse_than_dsgd(devices):
    # The reference's headline qualitative result (BASELINE.md): without
    # consensus, non-IID workers stagnate vs D-SGD on a good topology.
    h_no = GossipTrainer(_gossip_cfg(iid=False, gossip={"algorithm": "nocons"})).run(rounds=5)
    h_ds = GossipTrainer(_gossip_cfg(iid=False, gossip={
        "algorithm": "dsgd", "topology": "complete", "mode": "uniform"})).run(rounds=5)
    assert h_ds["avg_test_acc"][-1] > h_no["avg_test_acc"][-1] - 0.05


def test_centralized_preset_single_worker(devices):
    cfg = _gossip_cfg(gossip={"algorithm": "centralized"})
    tr = GossipTrainer(cfg)
    assert tr.num_workers == 1
    # original config object untouched (reference mutates shared args)
    assert cfg.data.num_users == 8
    h = tr.run(rounds=2)
    assert len(h) == 2


def test_fedlcon_multi_sweep(devices):
    cfg = _gossip_cfg(gossip={"algorithm": "fedlcon", "eps": 3,
                              "topology": "circle", "mode": "metropolis"})
    tr = GossipTrainer(cfg)
    h = tr.run(rounds=2)
    assert len(h) == 2


def test_gossip_learning_pairwise(devices):
    cfg = _gossip_cfg(gossip={"algorithm": "gossip"})
    tr = GossipTrainer(cfg)
    h = tr.run(rounds=3)
    assert h["avg_test_acc"][-1] > 0.5


def test_workers_fold_onto_devices(devices):
    # 16 workers on 8 devices: 2 lanes per device.
    tr = GossipTrainer(_gossip_cfg(num_users=16))
    assert tr.mesh.size == 8
    h = tr.run(rounds=2)
    assert len(h) == 2


@pytest.mark.parametrize("algorithm", ["fedavg", "fedprox", "fedadmm",
                                       "scaffold"])
def test_federated_learns(devices, algorithm):
    tr = FederatedTrainer(_fed_cfg(algorithm))
    h = tr.run(rounds=4)
    assert h["test_acc"][-1] > 0.6, h["test_acc"]


def test_scaffold_first_round_matches_fedavg(devices):
    # With zero-initialised control variates the SCAFFOLD gradient edit
    # is exactly zero, so round 1 must be bit-compatible with FedAvg
    # (same seed → same client sample, same batch plan).
    import jax
    a = FederatedTrainer(_fed_cfg("fedavg"))
    b = FederatedTrainer(_fed_cfg("scaffold"))
    a.run(rounds=1)
    b.run(rounds=1)
    for x, y in zip(jax.tree.leaves(jax.device_get(a.theta)),
                    jax.tree.leaves(jax.device_get(b.theta))):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=1e-6)


def test_scaffold_controls_mean_is_server_control(devices):
    # frac=1, zero init: after round 1, c = mean_i c_i⁺ exactly.
    import jax
    cfg = _fed_cfg("scaffold")
    cfg = dataclasses.replace(
        cfg, federated=dataclasses.replace(cfg.federated, frac=1.0))
    tr = FederatedTrainer(cfg)
    tr.run(rounds=1)
    ci = jax.device_get(tr.duals)
    c = jax.device_get(tr.c_global)
    for a, b in zip(jax.tree.leaves(ci), jax.tree.leaves(c)):
        np.testing.assert_allclose(np.asarray(a).mean(axis=0), np.asarray(b),
                                   atol=1e-5)
    # and the controls actually moved
    assert any(float(np.abs(np.asarray(l)).max()) > 0
               for l in jax.tree.leaves(c))


def test_federated_partial_participation_mask(devices):
    tr = FederatedTrainer(_fed_cfg("fedavg"))
    mask = tr.sample_clients(0.25)
    assert mask.sum() == 2  # max(int(0.25*8),1)
    mask = tr.sample_clients(0.01)
    assert mask.sum() == 1  # at least one client


def test_fedadmm_duals_update_only_sampled(devices):
    import jax
    tr = FederatedTrainer(_fed_cfg("fedadmm"))
    duals_before = jax.device_get(tr.duals)
    tr.run(rounds=1)
    duals_after = jax.device_get(tr.duals)
    # at least one dual leaf must have moved for sampled workers
    moved = any(
        not np.allclose(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(duals_before), jax.tree.leaves(duals_after))
    )
    assert moved


def test_round_counter_persists_across_runs(devices):
    tr = GossipTrainer(_gossip_cfg())
    tr.run(rounds=2)
    tr.run(rounds=2)
    assert tr.round == 4
    assert [r["round"] for r in tr.history] == [0, 1, 2, 3]


def test_blocked_run_matches_per_round(devices):
    # The fused multi-round lax.scan block path must be bit-identical to
    # the per-round dispatch path (same plans, same matrices, same order).
    import jax

    a = GossipTrainer(_gossip_cfg())
    a.run(rounds=4)
    b = GossipTrainer(_gossip_cfg())
    b.run(rounds=4, block=2)
    fa = np.concatenate([np.ravel(x) for x in jax.tree.leaves(jax.device_get(a.params))])
    fb = np.concatenate([np.ravel(x) for x in jax.tree.leaves(jax.device_get(b.params))])
    np.testing.assert_array_equal(fa, fb)
    la = [r["avg_train_loss"] for r in a.history.rows]
    lb = [r["avg_train_loss"] for r in b.history.rows]
    np.testing.assert_allclose(la, lb, rtol=1e-6)
    # Same eval cadence AND same eval values (phase order matches:
    # consensus -> eval -> local update in both paths).
    ea = [r["avg_test_acc"] for r in a.history.rows if "avg_test_acc" in r]
    eb = [r["avg_test_acc"] for r in b.history.rows if "avg_test_acc" in r]
    np.testing.assert_allclose(ea, eb, rtol=1e-6)
    # Remainder blocks (4 rounds, block=3 -> 3+1) also line up.
    c = GossipTrainer(_gossip_cfg())
    c.run(rounds=4, block=3)
    fc = np.concatenate([np.ravel(x) for x in jax.tree.leaves(jax.device_get(c.params))])
    np.testing.assert_array_equal(fa, fc)


def test_gossip_dropout_runs_and_learns(devices):
    tr = GossipTrainer(_gossip_cfg(gossip={"dropout": 0.3}))
    h = tr.run(rounds=4)
    assert h["avg_test_acc"][-1] > 0.5


def test_gossip_full_dropout_freezes_state(devices):
    import jax
    tr = GossipTrainer(_gossip_cfg(gossip={"dropout": 1.0}))
    before = jax.device_get(tr.params)
    tr.run(rounds=2)
    after = jax.device_get(tr.params)
    for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_gossip_dropout_blocked_matches_per_round(devices):
    import jax
    a = GossipTrainer(_gossip_cfg(gossip={"dropout": 0.4}))
    a.run(rounds=4)
    b = GossipTrainer(_gossip_cfg(gossip={"dropout": 0.4}))
    b.run(rounds=4, block=2)
    fa = np.concatenate([np.ravel(x) for x in jax.tree.leaves(jax.device_get(a.params))])
    fb = np.concatenate([np.ravel(x) for x in jax.tree.leaves(jax.device_get(b.params))])
    np.testing.assert_array_equal(fa, fb)


def test_fedlcon_faithful_bug_reproduces_single_sweep(devices):
    # The reference's FedLCon never clears new_weights across its eps
    # loop, so every sweep reloads sweep-0 results — effectively ONE
    # consensus sweep (simulators.py:189-196). faithful_bugs=True must
    # reproduce that exactly; the fixed path must differ.
    import jax

    def params_of(**gk):
        tr = GossipTrainer(_gossip_cfg(gossip=dict(
            algorithm="fedlcon", topology="circle", mode="metropolis", **gk)))
        tr.run(rounds=2)
        return np.concatenate([np.ravel(np.asarray(x))
                               for x in jax.tree.leaves(jax.device_get(tr.params))])

    buggy_eps3 = params_of(eps=3, faithful_bugs=True)
    one_sweep = params_of(eps=1)
    fixed_eps3 = params_of(eps=3)
    np.testing.assert_array_equal(buggy_eps3, one_sweep)
    assert not np.array_equal(fixed_eps3, one_sweep)


@pytest.mark.parametrize("algorithm", ["fedavg", "fedprox", "fedadmm",
                                       "scaffold"])
def test_compact_sampling_matches_full_width(devices, algorithm):
    # The gather-compact fast path must reproduce the full-width masked
    # path up to float summation order, for every algorithm, including
    # stale state on unsampled workers across rounds.
    import jax

    def run(compact):
        cfg = _fed_cfg(algorithm)
        cfg = cfg.replace(federated=dataclasses.replace(
            cfg.federated, compact=compact), mesh_devices=1)
        tr = FederatedTrainer(cfg)
        tr.run(rounds=3)
        return tr

    a = run(False)
    b = run(True)
    for x, y in zip(jax.tree.leaves(jax.device_get(a.theta)),
                    jax.tree.leaves(jax.device_get(b.theta))):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   atol=2e-5, rtol=1e-4)
    for x, y in zip(jax.tree.leaves(jax.device_get(a.params)),
                    jax.tree.leaves(jax.device_get(b.params))):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   atol=2e-5, rtol=1e-4)
    if a.duals is not None:
        for x, y in zip(jax.tree.leaves(jax.device_get(a.duals)),
                        jax.tree.leaves(jax.device_get(b.duals))):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       atol=2e-5, rtol=1e-4)
    np.testing.assert_allclose(a.history["test_acc"], b.history["test_acc"],
                               atol=1e-3)


@pytest.mark.parametrize("algorithm", ["fedavg", "fedadmm", "scaffold"])
def test_federated_blocked_matches_per_round(devices, algorithm):
    # The fused multi-round block path (lax.scan over rounds in one jit)
    # must reproduce the per-round path exactly: same client-sampling
    # sequence, same history rows, same final state.  Covers both the
    # full-width (sharded mesh) and compact (single-device) paths via
    # the default mesh.
    import jax

    def run(block):
        tr = FederatedTrainer(_fed_cfg(algorithm))
        tr.run(rounds=4, block=block)
        return tr

    a = run(1)
    b = run(2)
    c = run(3)  # remainder block: 3 + 1
    for other in (b, c):
        for x, y in zip(jax.tree.leaves(jax.device_get(a.theta)),
                        jax.tree.leaves(jax.device_get(other.theta))):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       atol=1e-6, rtol=1e-5)
        np.testing.assert_allclose(a.history["test_acc"],
                                   other.history["test_acc"], atol=1e-5)
        np.testing.assert_allclose(a.history["train_loss"],
                                   other.history["train_loss"], atol=1e-5)
        np.testing.assert_allclose(a.history["local_loss"],
                                   other.history["local_loss"], atol=1e-5)


def test_federated_blocked_compact_single_device(devices):
    # Compact + blocked on one device: sel gates are [k, m] index arrays.
    import jax

    def run(block):
        cfg = _fed_cfg("fedavg")
        cfg = cfg.replace(federated=dataclasses.replace(
            cfg.federated, compact=True), mesh_devices=1)
        tr = FederatedTrainer(cfg)
        tr.run(rounds=4, block=block)
        return tr

    a = run(1)
    b = run(4)
    for x, y in zip(jax.tree.leaves(jax.device_get(a.theta)),
                    jax.tree.leaves(jax.device_get(b.theta))):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   atol=1e-6, rtol=1e-5)
    np.testing.assert_allclose(a.history["test_acc"],
                               b.history["test_acc"], atol=1e-5)


def test_engines_reject_transformer_model(devices):
    cfg = _gossip_cfg()
    cfg = cfg.replace(model=dataclasses.replace(cfg.model, model="transformer"))
    with pytest.raises(ValueError, match="sequence model"):
        GossipTrainer(cfg)
    fcfg = _fed_cfg()
    fcfg = fcfg.replace(model=dataclasses.replace(fcfg.model, model="transformer"))
    with pytest.raises(ValueError, match="sequence model"):
        FederatedTrainer(fcfg)


def test_gossip_comm_compression_trains(devices):
    # bf16 on-the-wire consensus: the run proceeds and the consensus
    # still contracts disagreement (approximate mixing is still mixing).
    cfg = _gossip_cfg(gossip=dict(comm_dtype="bfloat16", rounds=3))
    tr = GossipTrainer(cfg)
    h = tr.run()
    assert len(h) == 3
    ref = GossipTrainer(_gossip_cfg()).run()
    assert abs(h.last()["avg_test_acc"] - ref.last()["avg_test_acc"]) < 0.1


def test_hierarchical_gossip_on_hybrid_mesh(devices):
    # DCN-aware schedule: intra-host rounds + periodic global mix, on a
    # 2x4 (hosts x ici) hybrid mesh.  The periodic global mix must
    # actually pull the hosts together: cross-worker spread under the
    # hierarchical schedule stays well below the no-communication run's.
    import jax

    def spread_of(tr):
        leaves = jax.tree.leaves(jax.device_get(tr.params))
        return max(float(np.abs(np.asarray(x) - np.asarray(x)[0]).max())
                   for x in leaves)

    cfg = _gossip_cfg(
        gossip=dict(topology="hierarchical", mode="metropolis", rounds=4,
                    hier_groups=2, hier_period=2),
        mesh_hosts=2, iid=False,
    )
    tr = GossipTrainer(cfg)
    h = tr.run()
    assert len(h) == 4

    nocons = GossipTrainer(_gossip_cfg(
        gossip=dict(algorithm="nocons", rounds=4), iid=False))
    nocons.run()
    assert spread_of(tr) < 0.5 * spread_of(nocons)


def test_federated_comm_compression_trains(devices):
    cfg = _fed_cfg("fedavg")
    cfg = cfg.replace(federated=dataclasses.replace(
        cfg.federated, comm_dtype="bfloat16"))
    tr = FederatedTrainer(cfg)
    h = tr.run(rounds=3)
    ref = FederatedTrainer(_fed_cfg("fedavg")).run(rounds=3)
    assert abs(h.last()["test_acc"] - ref.last()["test_acc"]) < 0.1


# ---------------------------------------------------------------------
# comm_impl: the ppermute shift path vs the dense all_gather path
# ---------------------------------------------------------------------

def _leaves(tr):
    import jax

    return [np.asarray(x) for x in jax.tree.leaves(jax.device_get(tr.params))]


def _shift_cfg(comm_impl, **kw):
    g = dict(mode="uniform", rounds=6, comm_impl=comm_impl)
    g.update(kw.pop("gossip", {}))
    return _gossip_cfg(gossip=g, **kw)


def test_comm_impl_shift_bitwise_equals_dense_uniform_ring(devices):
    """Full GossipTrainer.run, 8 workers on the 8-device mesh: the
    ppermute path must be BIT-identical to the dense path.  Uniform ring
    weights (1/2, 1/2) make every per-row product exact, so the two
    paths' different accumulation (gemm FMA vs mul+add) cannot round
    differently — any bit difference is a real routing bug."""
    td = GossipTrainer(_shift_cfg("dense"))
    ts = GossipTrainer(_shift_cfg("shift"))
    assert ts._shift_ids == (1, 7)
    hd, hs = td.run(), ts.run()
    assert hd.rows == hs.rows
    for a, b in zip(_leaves(td), _leaves(ts)):
        assert np.array_equal(a, b)


def test_comm_impl_shift_bitwise_equals_dense_dynamic_dropout(devices):
    """Time-varying single-edge graphs + dropout repair: per-round
    matrices (repaired as data) must stay inside the compiled shift set
    {0, 1, n-1} and match the dense path bit-for-bit (each row has at
    most one neighbor term, so no accumulation-order freedom exists)."""
    g = dict(topology="dynamic", mode="stochastic", dropout=0.3)
    td = GossipTrainer(_shift_cfg("dense", gossip=g))
    ts = GossipTrainer(_shift_cfg("shift", gossip=g))
    assert ts._shift_ids == (0, 1, 7)
    hd, hs = td.run(), ts.run()
    assert hd.rows == hs.rows
    for a, b in zip(_leaves(td), _leaves(ts)):
        assert np.array_equal(a, b)


def test_comm_impl_shift_close_for_stochastic_ring(devices):
    """Random (non-dyadic) ring weights: dense gemm uses FMA so the last
    bit can differ; the paths must agree to float32 rounding noise and
    produce identical history metrics."""
    g = dict(mode="stochastic")
    td = GossipTrainer(_shift_cfg("dense", gossip=g))
    ts = GossipTrainer(_shift_cfg("shift", gossip=g))
    hd, hs = td.run(), ts.run()
    for rd, rs in zip(hd.rows, hs.rows):
        assert rd.keys() == rs.keys()
        for k in rd:
            assert rd[k] == pytest.approx(rs[k], abs=1e-5)
    for a, b in zip(_leaves(td), _leaves(ts)):
        np.testing.assert_allclose(a, b, atol=2e-6, rtol=1e-5)


def test_comm_impl_shift_blocked_matches_per_round(devices):
    """The fused lax.scan block path must dispatch the same compiled
    shift mix: blocked vs per-round bit-equality, through run()."""
    ts = GossipTrainer(_shift_cfg("shift"))
    ts.run()
    tb = GossipTrainer(_shift_cfg("shift"))
    tb.run(block=3)
    assert ts.history.rows == tb.history.rows
    for a, b in zip(_leaves(ts), _leaves(tb)):
        assert np.array_equal(a, b)


def test_comm_impl_shift_choco_and_fedlcon(devices):
    """choco mixes its public copies x̂ through the same mix_once; fedlcon
    applies eps sweeps inside one jit — both must match dense exactly on
    uniform weights."""
    for g in (dict(algorithm="choco", rounds=4),
              dict(algorithm="fedlcon", eps=3, rounds=4)):
        td = GossipTrainer(_shift_cfg("dense", gossip=g))
        ts = GossipTrainer(_shift_cfg("shift", gossip=g))
        td.run(), ts.run()
        assert ts._shift_ids is not None
        for a, b in zip(_leaves(td), _leaves(ts)):
            assert np.array_equal(a, b)


def test_comm_impl_auto_and_validation(devices):
    # auto picks shift when the schedule's ppermute bytes beat the
    # all_gather with a 2x margin.
    assert GossipTrainer(_shift_cfg("auto"))._shift_ids == (1, 7)
    # complete graph on 8 workers: 7 rotations -> dense.
    assert GossipTrainer(_shift_cfg(
        "auto", gossip=dict(topology="complete")))._shift_ids is None
    # folded lanes: 16 workers on 8 devices (2 lanes each) still routes
    # the ring onto ppermutes — the straddling shifts {1, 15} each
    # consume ONE lane of their neighbor block, so only 2 lane-shards
    # move per device (shift_comm_lanes) vs 14 for the dense gather.
    assert GossipTrainer(_shift_cfg(
        "auto", num_users=16))._shift_ids == (1, 15)
    # folded complete graph: every device rotation needed -> dense.
    assert GossipTrainer(_shift_cfg(
        "auto", num_users=16,
        gossip=dict(topology="complete")))._shift_ids is None
    # 1-device mesh: no wire to save — auto must stay dense (the shift
    # path would materialise one sliced copy of the stacked state per
    # diagonal; a 32-worker random graph OOMs a single chip that way).
    assert GossipTrainer(_shift_cfg(
        "auto", mesh_devices=1))._shift_ids is None
    # dense shift set (random graph): local mix work is linear in the
    # diagonal count -> dense even though lanes fold.
    assert GossipTrainer(_shift_cfg(
        "auto", num_users=32,
        gossip=dict(topology="random", local_bs=8)))._shift_ids is None
    # explicit shift honors an expensive decomposition (complete = all 7).
    tr = GossipTrainer(_shift_cfg("shift", gossip=dict(topology="complete")))
    assert tr._shift_ids == tuple(range(1, 8))
    # explicit shift on a hybrid (non-flat) mesh must fail loudly.
    with pytest.raises(ValueError, match="comm_impl='shift'"):
        GossipTrainer(_shift_cfg("shift", mesh_hosts=2))
    with pytest.raises(ValueError, match="mixing-schedule algorithm"):
        GossipTrainer(_shift_cfg("shift", gossip=dict(algorithm="gossip")))
    with pytest.raises(ValueError, match="comm_impl"):
        GossipTrainer(_shift_cfg("nonsense"))


def test_comm_impl_shift_folded_lanes_bitwise_equals_dense(devices):
    """The north-star shape: 32 workers folded 4-per-device onto the
    8-device mesh.  The block-circulant decomposition (device ppermutes
    + lane slice) must be BIT-identical to the dense path through
    GossipTrainer.run on uniform ring weights."""
    kw = dict(num_users=32, gossip=dict(local_bs=8, rounds=4))
    td = GossipTrainer(_shift_cfg("dense", **kw))
    ts = GossipTrainer(_shift_cfg("shift", **kw))
    assert ts._shift_ids == (1, 31)
    assert ts.mesh.size == 8 and ts.num_workers == 32
    hd, hs = td.run(), ts.run()
    assert hd.rows == hs.rows
    for a, b in zip(_leaves(td), _leaves(ts)):
        assert np.array_equal(a, b)
    # auto routes this shape onto the shift path (the VERDICT r2 gap:
    # the flagship collective now reaches the flagship config).
    assert GossipTrainer(_shift_cfg("auto", **kw))._shift_ids == (1, 31)


def test_comm_impl_shift_folded_dynamic_dropout(devices):
    """Folded lanes + time-varying single-edge graphs + dropout repair:
    per-round coefficient tables must stay inside the compiled shift set
    and match dense bit-for-bit (rows have at most one neighbor term)."""
    g = dict(topology="dynamic", mode="stochastic", dropout=0.3,
             local_bs=8, rounds=4)
    td = GossipTrainer(_shift_cfg("dense", num_users=16, gossip=g))
    ts = GossipTrainer(_shift_cfg("shift", num_users=16, gossip=g))
    assert ts._shift_ids == (0, 1, 15)
    hd, hs = td.run(), ts.run()
    assert hd.rows == hs.rows
    for a, b in zip(_leaves(td), _leaves(ts)):
        assert np.array_equal(a, b)


# ---------------------------------------------------------------------
# Local train/val holdout (reference train_val_test semantics)
# ---------------------------------------------------------------------

def _holdout_gossip_cfg(block=1, holdout=0.1):
    return _gossip_cfg(
        gossip=dict(mode="uniform", rounds=3, local_ep=2,
                    block_rounds=block),
        data_extra=dict(local_holdout=holdout, holdout_mode="random"),
    )


def test_gossip_holdout_trains_on_subshard_with_client_history(devices):
    tr = GossipTrainer(_holdout_gossip_cfg())
    tr.run()
    w, l = tr.index_matrix.shape
    val_size = max(int(l * 0.1), 1)
    assert tr._train_matrix.shape == (w, l - val_size)
    # every batch-plan index must come from the train sub-shard
    from dopt.data import make_batch_plan
    plan = make_batch_plan(tr._train_matrix, batch_size=32, local_ep=2,
                           seed=tr.cfg.seed, round_idx=0)
    for i in range(w):
        assert set(plan.idx[i].ravel()) <= set(tr._train_matrix[i])
    # per-epoch per-worker rows, P2 schema
    rows = tr.client_history.rows
    assert len(rows) == 3 * w * 2
    assert set(rows[0]) == {"round", "iter", "worker", "train_loss",
                            "train_acc", "val_acc", "val_loss"}
    # blocked run: identical history and client rows
    tb = GossipTrainer(_holdout_gossip_cfg(block=3))
    tb.run()
    assert tb.history.rows == tr.history.rows
    assert tb.client_history.rows == rows


def test_federated_holdout_client_history_sampled_only(devices):
    import dataclasses as _dc

    def fed(compact=None, mesh_devices=None):
        cfg = _fed_cfg("fedavg")
        cfg = cfg.replace(
            data=_dc.replace(cfg.data, local_holdout=0.1,
                             holdout_mode="deterministic"),
            federated=_dc.replace(cfg.federated, compact=compact),
            mesh_devices=mesh_devices,
        )
        return cfg

    tr = FederatedTrainer(fed())
    tr.run(rounds=3)
    rows = tr.client_history.rows
    m = max(int(0.5 * 8), 1)
    assert len(rows) == 3 * m * 1  # local_ep=1
    assert set(rows[0]) == {"global_round", "epoch", "worker", "train_loss",
                            "train_acc", "val_acc", "val_loss"}
    # only sampled workers appear per round
    for t in range(3):
        assert len([r for r in rows if r["global_round"] == t]) == m
    # compact path (1-device) produces the same rows
    tc = FederatedTrainer(fed(compact=True, mesh_devices=1))
    tc.run(rounds=3)
    assert [r["worker"] for r in tc.client_history.rows] == [
        r["worker"] for r in rows]
    for a, b in zip(tc.client_history.rows, rows):
        assert a["val_acc"] == pytest.approx(b["val_acc"], abs=1e-6)
        assert a["train_loss"] == pytest.approx(b["train_loss"], abs=1e-5)


def test_holdout_resume_preserves_client_history(devices, tmp_path):
    tr = GossipTrainer(_holdout_gossip_cfg())
    tr.run(rounds=2)
    tr.save(tmp_path / "ck")
    tr2 = GossipTrainer(_holdout_gossip_cfg())
    tr2.restore(tmp_path / "ck")
    assert tr2.client_history.rows == tr.client_history.rows
    tr2.run(rounds=1)
    tr.run(rounds=1)
    assert tr2.client_history.rows == tr.client_history.rows


# ---------------------------------------------------------------------
# No dead config knobs: every field changes behavior or raises
# ---------------------------------------------------------------------

def test_weight_decay_changes_training(devices):
    import jax

    def fed(wd):
        cfg = _fed_cfg("fedavg")
        return cfg.replace(optim=dataclasses.replace(cfg.optim,
                                                     weight_decay=wd))

    a = FederatedTrainer(fed(0.0)); a.run(rounds=2)
    b = FederatedTrainer(fed(0.1)); b.run(rounds=2)
    la = jax.tree.leaves(jax.device_get(a.theta))
    lb = jax.tree.leaves(jax.device_get(b.theta))
    assert any(not np.allclose(np.asarray(x), np.asarray(y))
               for x, y in zip(la, lb))
    # the ℓ2 term shrinks the solution norm
    na = sum(float((np.asarray(x) ** 2).sum()) for x in la)
    nb = sum(float((np.asarray(x) ** 2).sum()) for x in lb)
    assert nb < na

    def gos(wd):
        cfg = _gossip_cfg()
        return cfg.replace(optim=dataclasses.replace(cfg.optim,
                                                     weight_decay=wd))

    ga = GossipTrainer(gos(0.0)); ga.run(rounds=2)
    gb = GossipTrainer(gos(0.1)); gb.run(rounds=2)
    assert any(
        not np.allclose(np.asarray(x), np.asarray(y))
        for x, y in zip(jax.tree.leaves(jax.device_get(ga.params)),
                        jax.tree.leaves(jax.device_get(gb.params))))


def test_unknown_optimizer_rejected(devices):
    cfg = _fed_cfg("fedavg")
    cfg = cfg.replace(optim=dataclasses.replace(cfg.optim, optimizer="adam"))
    with pytest.raises(ValueError, match="optimizer"):
        FederatedTrainer(cfg)
    cfg = _gossip_cfg()
    cfg = cfg.replace(optim=dataclasses.replace(cfg.optim, optimizer="adam"))
    with pytest.raises(ValueError, match="optimizer"):
        GossipTrainer(cfg)


def test_param_dtype_controls_state_storage(devices):
    import jax
    import jax.numpy as jnp

    cfg = _gossip_cfg()
    cfg = cfg.replace(model=dataclasses.replace(cfg.model,
                                                param_dtype="bfloat16"))
    tr = GossipTrainer(cfg)
    assert all(x.dtype == jnp.bfloat16 for x in jax.tree.leaves(tr.params))
    assert all(x.dtype == jnp.bfloat16 for x in jax.tree.leaves(tr.momentum))
    h = tr.run(rounds=2)
    assert len(h) == 2
    assert all(x.dtype == jnp.bfloat16 for x in jax.tree.leaves(tr.params))

    fcfg = _fed_cfg("fedadmm")
    fcfg = fcfg.replace(model=dataclasses.replace(fcfg.model,
                                                  param_dtype="bfloat16"))
    ft = FederatedTrainer(fcfg)
    assert all(x.dtype == jnp.bfloat16 for x in jax.tree.leaves(ft.theta))
    assert all(x.dtype == jnp.bfloat16 for x in jax.tree.leaves(ft.duals))
    ft.run(rounds=1)
    assert all(x.dtype == jnp.bfloat16 for x in jax.tree.leaves(ft.theta))


def test_from_reference_args_rejects_unequal(devices):
    from dopt.config import from_reference_args

    with pytest.raises(ValueError, match="unequal"):
        from_reference_args({"dataset": "mnist", "unequal": True})
    cfg = from_reference_args({"dataset": "mnist"})
    assert not hasattr(cfg.data, "unequal")


def test_sharded_eval_mode_tracks_full(devices):
    """eval_mode='sharded' must produce per-round fleet-mean metrics
    close to the full-set eval (unbiased 1/W-shard estimate) and leave
    trainer.evaluate() at reference full-set semantics."""
    accs = {}
    for mode in ("full", "sharded"):
        tr = GossipTrainer(_gossip_cfg(
            gossip={"eval_mode": mode}, iid=False))
        h = tr.run(rounds=4)
        accs[mode] = [r["avg_test_acc"] for r in h if "avg_test_acc" in r]
        # evaluate() is full-set in both modes: per-worker counts equal
        # the whole test split (128 in _gossip_cfg).
        ev = tr.evaluate()
        assert int(ev["count"][0]) == 128
    assert abs(accs["full"][-1] - accs["sharded"][-1]) < 0.12, accs


def test_sharded_eval_composes_with_dropout_and_choco(devices):
    """The sharded evaluator must slot into the same block program as
    fault injection and CHOCO compression (shape contract: [W]-dict)."""
    tr = GossipTrainer(_gossip_cfg(gossip={
        "eval_mode": "sharded", "dropout": 0.25}))
    h = tr.run(rounds=3)
    assert any("avg_test_acc" in r for r in h)
    tr2 = GossipTrainer(_gossip_cfg(gossip={
        "algorithm": "choco", "eval_mode": "sharded",
        "compression": "topk", "compression_ratio": 1.0}))
    h2 = tr2.run(rounds=3)
    assert any("avg_test_acc" in r for r in h2)


def test_eval_mode_validation():
    with pytest.raises(ValueError, match="eval_mode"):
        GossipTrainer(_gossip_cfg(gossip={"eval_mode": "bogus"}))


def _plain_eval(apply, params, x, y, batch):
    """One worker on the whole test split, by hand: the share of rows it
    labels right, and the reference notebooks' loss (the mean over test
    batches of each batch's mean cross-entropy; the last batch holds only
    the rows that are left)."""
    logits = np.asarray(apply(params, x), np.float64)
    logp = logits - logits.max(-1, keepdims=True)
    logp = logp - np.log(np.exp(logp).sum(-1, keepdims=True))
    nll = -logp[np.arange(len(y)), y]
    losses = [nll[s:s + batch].mean() for s in range(0, len(y), batch)]
    return float((logits.argmax(-1) == y).mean()), float(np.mean(losses))


@pytest.mark.parametrize("model", ["mlp", "resnet18"])
def test_round_eval_is_every_worker_on_the_whole_test_split(devices, model):
    """A round's ``avg_test_acc`` / ``avg_test_loss`` (``eval_mode``
    full, every round) are each worker's own parameters after the round's
    mix, on every row of the test split and on nothing else (300 rows:
    the second batch of 256 is mostly padding), averaged over the workers.
    ``resnet18`` takes the grouped stacked forward, as the benchmark's
    ResNet cells do; ``mlp`` the vmapped one."""
    import jax

    cfg = _gossip_cfg(num_users=4, iid=False)
    if model == "resnet18":
        cfg = dataclasses.replace(cfg, model=ModelConfig(
            model="resnet18", stage_sizes=(1, 1), faithful=False,
            input_shape=(8, 8, 3)))
    cfg = dataclasses.replace(
        cfg, mesh_devices=1,
        data=dataclasses.replace(cfg.data, synthetic_train_size=64,
                                 synthetic_test_size=300),
        gossip=dataclasses.replace(cfg.gossip, local_bs=8))
    tr = GossipTrainer(cfg, eval_every=1)
    tr.run(rounds=1)
    before = jax.device_get(tr.params)
    w = np.asarray(tr.mixing.for_round(1), np.float64)
    row = [r for r in tr.run(rounds=1) if r["round"] == 1][0]

    x, y = tr.dataset.test_x, np.asarray(tr.dataset.test_y)
    assert len(y) == 300
    apply = jax.jit(lambda p, x: tr.model.apply({"params": p}, x))
    per_worker = []
    for i in range(cfg.data.num_users):
        mixed = jax.tree.map(
            lambda leaf: np.tensordot(w[i], np.asarray(leaf, np.float64),
                                      axes=1).astype(np.float32), before)
        per_worker.append(_plain_eval(apply, mixed, x, y, batch=256))
    acc, loss = np.mean(per_worker, axis=0)
    # Workers that differ, so that one of them evaluated for all would show.
    assert len({round(a, 6) for a, _ in per_worker}) > 1, per_worker
    assert row["avg_test_acc"] == pytest.approx(acc, abs=1 / (4 * len(y)))
    assert row["avg_test_loss"] == pytest.approx(loss, rel=1e-4)
