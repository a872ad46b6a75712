"""The seams a sequence configuration comes through by files alone, on a
toy one under ``tests/data/`` (no published model): the objective by
file, a reference whose fleet lives on the host, the parity cut and its
tolerance as data, the rehearsal's overrides, and the dry run of adding
the toy as a cell."""

import dataclasses
import importlib.util
import json
import shutil
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import flops, parity, reference

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
HIGHEST = jax.default_matmul_precision("highest")


def load_file(path):
    spec = importlib.util.spec_from_file_location(path.stem, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def seq_toy():
    return load_file(DATA / "reference_models" / "seq_toy.py")


def token_rounds(seed, *, workers, steps=2, rows=3, positions=12, vocab=48,
                 rounds=2, fedavg=False):
    """Rounds of [workers, steps, rows, positions] token batches: next-token
    labels, the last position and a few others not counted, one padding
    row a worker."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(rounds):
        x = rng.integers(0, vocab, (workers, steps, rows, positions),
                         dtype=np.int32)
        y = np.roll(x, -1, axis=-1)
        y[..., -1] = -1
        y[rng.random(y.shape) < 0.1] = -1
        w = np.ones((workers, steps, rows), np.float32)
        w[:, -1, -1] = 0.0
        entry = {"bx": x, "by": y, "bw": w}
        if fedavg:
            entry["sel"] = sorted(int(c) for c in rng.choice(
                3 * workers, workers, replace=False))
        else:
            m = rng.random((workers, workers)).astype(np.float32)
            entry["w"] = m / m.sum(1, keepdims=True)
        out.append(entry)
    return out


def assert_trees_equal(a, b):
    jax.tree.map(lambda x, y: np.testing.assert_array_equal(
        np.asarray(x), np.asarray(y)), a, b)


# ---------------------------------------------------------------- objective

def plain_step(objective, lr, mu):
    """Momentum SGD written out here, nothing donated."""
    @jax.jit
    def step(p, buf, x, y, w):
        g = jax.grad(objective)(p, x, y, w)
        buf = jax.tree.map(lambda b, g: mu * b + g, buf, g)
        return jax.tree.map(lambda p, b: p - lr * b, p, buf), buf
    return step


def test_token_cross_entropy_keeps_the_contract():
    rng = np.random.default_rng(0)
    logits = rng.standard_normal((3, 5, 7)).astype(np.float32)
    labels = rng.integers(0, 7, (3, 5)).astype(np.int32)
    labels[0, 4] = labels[1, 2] = -1
    weights = np.array([1.0, 1.0, 0.0], np.float32)      # row 2 is padding
    logp = logits - np.log(np.exp(logits).sum(-1, keepdims=True))
    nll = [-logp[b, t, labels[b, t]] for b in (0, 1) for t in range(5)
           if labels[b, t] >= 0]
    assert len(nll) == 8
    got = reference.token_cross_entropy(logits, labels, weights)
    assert float(got) == pytest.approx(sum(nll) / 8, rel=1e-6)
    # nothing counted: the count is held to at least 1, the loss is 0
    none = reference.token_cross_entropy(logits, labels, np.zeros(3, np.float32))
    assert float(none) == 0.0


def test_the_files_objective_is_the_one_loaded():
    toy = seq_toy()
    assert reference.objective_of(toy) is toy.objective
    params = toy.init(1)
    r = token_rounds(1, workers=1)[0]
    x, y, w = r["bx"][0, 0], r["by"][0, 0], r["bw"][0, 0]
    plain = reference.token_cross_entropy(toy.forward(params, x), y, w)
    # the router's balance term is inside the objective
    assert float(toy.objective(params, x, y, w)) > float(plain)


@pytest.mark.parametrize("model", ["model1_faithful", "resnet18_groupnorm"])
def test_the_shipped_references_bring_no_objective(model):
    assert not hasattr(reference.load_module(model), "objective")


def test_without_an_objective_the_step_is_the_old_composition():
    """``forward`` alone: the loss is ``weighted_cross_entropy`` of it, and
    the step equals the one the harness had, to the bit."""
    model = "model1_faithful"
    forward = reference.load_module(model).forward
    rng = np.random.default_rng(0)
    params = {
        "conv1": {"kernel": rng.standard_normal((5, 5, 1, 32)) * 0.1,
                  "bias": np.zeros(32)},
        "conv2": {"kernel": rng.standard_normal((5, 5, 32, 64)) * 0.03,
                  "bias": np.zeros(64)},
        "fc1": {"kernel": rng.standard_normal((3136, 512)) * 0.02,
                "bias": np.zeros(512)},
        "fc2": {"kernel": rng.standard_normal((512, 10)) * 0.05,
                "bias": np.zeros(10)}}
    params = jax.tree.map(lambda a: a.astype(np.float32), params)
    x = rng.standard_normal((2, 6, 28, 28, 1)).astype(np.float32)
    y = rng.integers(0, 10, (2, 6)).astype(np.int32)
    w = np.ones((2, 6), np.float32)
    w[1, -1] = 0.0

    def old_step(params, buf, x, y, w):          # PR 22's make_step, verbatim
        def loss_fn(p):
            return reference.weighted_cross_entropy(forward(p, x), y, w)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        buf = jax.tree.map(lambda b, g: 0.5 * b + g, buf, grads)
        params = jax.tree.map(lambda p, b: p - 0.01 * b, params, buf)
        return params, buf, loss

    with HIGHEST:
        new = reference.make_step(reference.load_objective(model), lr=0.01,
                                  momentum=0.5)
        old = jax.jit(old_step)
        a = b = (params, jax.tree.map(np.zeros_like, params))
        for s in range(2):
            a = new(*jax.tree.map(jnp.array, a), x[s], y[s], w[s])
            b = old(*b, x[s], y[s], w[s])
            assert_trees_equal(a, b)
            a, b = a[:2], b[:2]


def test_gossip_with_the_files_objective_equals_a_loop_written_here():
    toy = seq_toy()
    init, rounds = toy.init(2), token_rounds(2, workers=4)
    got = reference.run_gossip(toy.objective, init, rounds, lr=0.05,
                               momentum=0.5)
    with HIGHEST:
        step = plain_step(toy.objective, 0.05, 0.5)
        ps = [init] * 4
        bufs = [jax.tree.map(jnp.zeros_like, init)] * 4
        for r in rounds:
            fleet = jax.tree.map(lambda *xs: jnp.stack(xs), *ps)
            fleet = jax.tree.map(
                lambda x: jnp.tensordot(jnp.asarray(r["w"]), x, axes=1), fleet)
            ps = [jax.tree.map(lambda x, i=i: x[i], fleet) for i in range(4)]
            for i in range(4):
                for s in range(r["bx"].shape[1]):
                    ps[i], bufs[i] = step(ps[i], bufs[i], r["bx"][i, s],
                                          r["by"][i, s], r["bw"][i, s])
    assert len(got) == 4
    for g, p in zip(got, ps):
        assert_trees_equal(g, p)
    # and the job trained: every leaf of worker 0 moved
    assert all(np.abs(a - b).max() > 0 for a, b in
               zip(jax.tree.leaves(got[0]), jax.tree.leaves(init)))


def test_fedavg_with_the_files_objective_equals_a_loop_written_here():
    toy = seq_toy()
    init = toy.init(3)
    rounds = token_rounds(3, workers=3, rounds=3, fedavg=True)
    got = reference.run_fedavg(toy.objective, init, rounds, lr=0.05,
                               momentum=0.5)
    with HIGHEST:
        step = plain_step(toy.objective, 0.05, 0.5)
        theta = jax.tree.map(jnp.asarray, init)
        bufs = {c: jax.tree.map(jnp.zeros_like, init) for c in range(9)}
        for r in rounds:
            local = []
            for k, c in enumerate(r["sel"]):
                p = theta
                for s in range(r["bx"].shape[1]):
                    p, bufs[c] = step(p, bufs[c], r["bx"][k, s],
                                      r["by"][k, s], r["bw"][k, s])
                local.append(p)
            theta = jax.tree.map(lambda a, b, c: (a + b + c) / 3, *local)
    assert_trees_equal(got, theta)
    # some client was sampled twice, so its momentum was carried over
    seen = [c for r in rounds for c in r["sel"]]
    assert len(set(seen)) < len(seen)


# ---------------------------------------------------- a reference that fits

def device_bytes():
    return sum(a.nbytes for a in jax.live_arrays())


@pytest.mark.parametrize("engine", ["gossip", "fedavg"])
def test_between_turns_the_fleet_is_on_the_host(engine, monkeypatch):
    """When a worker's turn starts, the chip holds none of the other
    workers' state: at most one worker's and one leaf of the fleet."""
    toy = seq_toy()
    init = toy.init(4)
    workers = 6
    leaves = jax.tree.leaves(init)
    one_worker = 2 * sum(x.nbytes for x in leaves)      # parameters, momentum
    fleet_leaf = workers * max(x.nbytes for x in leaves)
    before = device_bytes()
    seen = []
    turn = reference._local_steps

    def spy(*args):
        seen.append(device_bytes() - before)
        out = turn(*args)
        assert all(isinstance(x, np.ndarray) for x in jax.tree.leaves(out))
        return out

    monkeypatch.setattr(reference, "_local_steps", spy)
    rounds = token_rounds(4, workers=workers, fedavg=engine == "fedavg")
    run = reference.run_gossip if engine == "gossip" else reference.run_fedavg
    out = run(toy.objective, init, rounds, lr=0.05, momentum=0.5)
    assert len(seen) == 2 * workers
    assert max(seen) <= one_worker + fleet_leaf
    # what the fleet held whole would keep there: n workers' state
    assert max(seen) < workers * one_worker / 2
    assert all(isinstance(x, np.ndarray) for x in jax.tree.leaves(out))


# ------------------------------------------------------- the cut, the limit

def cell(name):
    from benchmark import adapter
    from benchmark.run import load_cell

    c = load_cell(name)
    chips = c["chips"]
    return c, adapter.build_config(name, c["config"], c["traffic"], seed=5,
                                   chips=chips)


def old_parity_config(cfg, traffic):
    """PR 22's ``adapter.parity_config``, verbatim."""
    cut = traffic["parity"]
    engine = traffic["engine"]
    sec = getattr(cfg, engine)
    bs = min(cut.get("local_bs", sec.local_bs), sec.local_bs)
    optim = dataclasses.replace(cfg.optim, lr=cut.get("lr", cfg.optim.lr))
    rows = cut["steps_per_epoch"] * bs
    return cfg.replace(
        name=cfg.name + ".parity",
        data=dataclasses.replace(
            cfg.data, synthetic_train_size=rows * cfg.data.num_users,
            synthetic_test_size=min(cfg.data.synthetic_test_size, 256)),
        model=dataclasses.replace(cfg.model, compute_dtype="float32"),
        optim=optim,
        **{engine: dataclasses.replace(sec, local_ep=cut["local_ep"],
                                       local_bs=bs)})


# The four cells PR 27's seams were written for; the decoder cells bring
# their own cut (the stated precision) and their own tolerance.
@pytest.mark.parametrize("name", ["resnet18.gossip32.chip1",
                                  "resnet18.gossip32.chip4",
                                  "model1.gossip160-ring",
                                  "model1.fedavg128"])
def test_the_shipped_cut_is_the_old_parity_job(name):
    from benchmark import adapter

    c, cfg = cell(name)
    assert c["traffic"]["parity"]["compute_dtype"] == "float32"
    assert adapter.parity_config(cfg, c["traffic"]) \
        == old_parity_config(cfg, c["traffic"])
    assert "parity_tolerance" not in c["config"]
    assert parity.tolerance(c["config"]) == {
        "value": 5e-5, "of": "abs", "why": parity.TOLERANCE_WHY}


@pytest.mark.parametrize("name", ["resnet18.gossip32.chip1",
                                  "model1.fedavg128"])
def test_an_empty_cut_leaves_the_job_alone(name):
    from benchmark import adapter

    c, cfg = cell(name)
    got = adapter.parity_config(cfg, {**c["traffic"], "parity": {}})
    assert got == cfg.replace(name=cfg.name + ".parity")
    assert got.model.compute_dtype == c["config"]["guarantees"]["compute_dtype"]
    # each key cuts what it names and nothing else
    sec = getattr(cfg, c["traffic"]["engine"])
    one = adapter.parity_config(
        cfg, {**c["traffic"], "parity": {"local_ep": sec.local_ep + 1}})
    assert getattr(one, c["traffic"]["engine"]).local_ep == sec.local_ep + 1
    assert one.data == cfg.data and one.model == cfg.model
    assert one.optim == cfg.optim


def test_parity_tolerance_is_the_configurations_and_needs_its_why():
    config = json.loads((DATA / "configs" / "seq-toy.json").read_text())
    tol = parity.tolerance(config)
    assert tol["value"] == 1e-3 and tol["of"] == "moved"
    assert tol["why"] == config["parity_tolerance"]["why"]
    for broken in ({"value": 1e-3, "of": "moved"},
                   {"value": 1e-3, "of": "moved", "why": ""},
                   {"value": 1e-3, "of": "moved", "why": "x" * 201},
                   {"value": 1e-3, "of": "moved", "why": "two\nlines"}):
        with pytest.raises(ValueError, match="why"):
            parity.tolerance({"parity_tolerance": broken})
    with pytest.raises(ValueError, match="abs"):
        parity.tolerance({"parity_tolerance": {
            "value": 1e-3, "of": "relative", "why": "because"}})
    with pytest.raises(ValueError, match="abs"):
        parity.tolerance({"parity_tolerance": {
            "value": 1e-3, "of": "abs", "why": "because", "slack": 2}})


def test_a_moved_tolerance_is_held_against_the_movement(monkeypatch):
    """``parity.run`` on the rehearsal's toy of a shipped cell, the
    configuration given a tolerance of its own: ``compared`` is the error
    over the reference's movement, and ``why`` comes back with it."""
    from benchmark import adapter

    c, cfg = cell("model1.gossip160-ring")
    cfg = adapter.rehearsal_config(cfg, c["traffic"])
    plain = parity.run(cfg, c["config"], c["traffic"])
    assert plain["of"] == "abs" and plain["compared"] == plain["error"]
    assert plain["tolerance"] == parity.TOLERANCE and plain["ok"]
    config = {**c["config"], "parity_tolerance": {
        "value": 1e-12, "of": "moved", "why": "far too tight, on purpose"}}
    held = parity.run(cfg, config, c["traffic"])
    assert held["error"] == plain["error"] and held["moved"] == plain["moved"]
    assert held["compared"] == held["error"] / held["moved"]
    assert held["why"] == "far too tight, on purpose" and not held["ok"]


# ------------------------------------------------------------ the rehearsal

def test_rehearsal_overrides_are_the_configurations():
    from benchmark import adapter

    c, cfg = cell("model1.fedavg128")
    plain = adapter.rehearsal_config(cfg, c["traffic"])
    assert adapter.rehearsal_config(cfg, c["traffic"], None) == plain
    assert adapter.rehearsal_config(cfg, c["traffic"], {}) == plain
    small = adapter.rehearsal_config(
        cfg, c["traffic"], {"model": {"num_classes": 4, "input_shape": [8, 8, 1]},
                            "data": {"synthetic_test_size": 8}})
    assert small.model.num_classes == 4
    assert small.model.input_shape == (8, 8, 1)
    assert small.model.compute_dtype == "float32"
    assert small.data.synthetic_test_size == 8
    assert small.data.num_users == plain.data.num_users
    assert small.federated == plain.federated
    with pytest.raises(TypeError, match="experts_held"):     # dopt's refusal
        adapter.rehearsal_config(cfg, c["traffic"],
                                 {"model": {"experts_held": 2}})
    with pytest.raises(KeyError, match="optim"):
        adapter.rehearsal_config(cfg, c["traffic"], {"optim": {"lr": 1.0}})


# ------------------------------------------------------------------ run.py

def test_the_harness_keeps_the_programs_own_spans():
    from benchmark import run

    assert set(run.HOST_SPANS) == {
        "bench.run_call", "host_batch_plan", "round_step", "round_dispatch",
        "round_wait", "round_fetch", "round_record"}
    assert not hasattr(run, "TraceSpans")
    assert "timers.tracer" not in Path(run.__file__).read_text()


# ----------------------------------------------------------------- dry run

def test_the_toy_becomes_a_cell_by_files_and_entries_alone(tmp_path,
                                                           monkeypatch):
    """A copy of the yardstick, the toy's three files added, one ``configs``
    and one ``workloads`` entry: the cell resolves, its operations and
    parameters count, its configuration builds, and the first refusal is
    the program's own."""
    from benchmark import adapter, run

    bench = tmp_path / "benchmark"
    for d in ("configs", "traffic"):
        shutil.copytree(ROOT / "benchmark" / d, bench / d)
        for f in (DATA / d).iterdir():
            shutil.copy(f, bench / d / f.name)
    entries = dict(BENCH)
    entries["configs"] = BENCH["configs"] + [{
        "name": "seq-toy", "source": "none: a toy",
        "file": "benchmark/configs/seq-toy.json",
        "reduced": ["experts_held", "vocab_rows"], "why": "a toy"}]
    entries["workloads"] = BENCH["workloads"] + [{
        "name": "seq-toy.gossip4", "config": "seq-toy",
        "traffic": "seq-toy-gossip4", "chips": 1, "why": "a toy"}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(entries))
    monkeypatch.setattr(run, "ROOT", tmp_path)
    monkeypatch.setattr(run, "HERE", bench)

    c = run.load_cell("seq-toy.gossip4")
    config, traffic = c["config"], c["traffic"]
    assert flops.param_count(config["layers"]) == config["parameters"]
    assert flops.round_flops(config["layers"], train_samples=24,
                             eval_forwards=0) == 2 * 213_504 * 72
    assert [m["name"] for m in c["end_to_end"]] == [
        "train_samples_per_s", "round_ms_p50", "setup_s"]
    cfg = adapter.build_config(c["name"], config, traffic, seed=7, chips=1)
    small = adapter.rehearsal_config(cfg, traffic, config["rehearsal"])
    assert small.model.num_classes == 16 and small.model.input_shape == (6,)
    assert adapter.parity_config(cfg, traffic) \
        == cfg.replace(name=cfg.name + ".parity")
    with pytest.raises(ValueError, match="sequence model"):
        adapter.build_trainer(small, traffic, config)
