"""The round's anatomy from inside the program (dopt.utils.profiling):
the host span tree on the profiler's clock, the step annotation per
round, and the device scopes in the compiled round programs' metadata.
"""

import contextlib
import dataclasses
import os
import re
import subprocess
import sys
import time
from pathlib import Path

import jax
import pytest
from jax._src.config import enable_compilation_cache

from dopt.config import (DataConfig, ExperimentConfig, FederatedConfig,
                         GossipConfig, ModelConfig, OptimizerConfig)
from dopt.engine import FederatedTrainer, GossipTrainer
from dopt.utils.profiling import ROUND_STEP, PhaseTimers

REPO = Path(__file__).resolve().parent.parent
SPANS = ("host_batch_plan", "round_step", "round_dispatch", "round_wait",
         "round_fetch", "round_record")

_DATA = DataConfig(dataset="synthetic", num_users=8, iid=True,
                   synthetic_train_size=256, synthetic_test_size=64)
_MODEL = ModelConfig(model="mlp", input_shape=(28, 28, 1), faithful=False)
_OPTIM = OptimizerConfig(lr=0.1, momentum=0.5)


def _gossip_cfg(**data) -> ExperimentConfig:
    return ExperimentConfig(
        name="anatomy-gossip", seed=3,
        data=dataclasses.replace(_DATA, **data), model=_MODEL, optim=_OPTIM,
        gossip=GossipConfig(algorithm="dsgd", topology="circle",
                            mode="metropolis", local_ep=2, local_bs=32))


def _fed_cfg(frac: float, **top) -> ExperimentConfig:
    return ExperimentConfig(
        name="anatomy-fed", seed=3, data=_DATA, model=_MODEL, optim=_OPTIM,
        federated=FederatedConfig(algorithm="fedavg", frac=frac, rounds=2,
                                  local_ep=1, local_bs=32), **top)


# ------------------------------------------------------------ (a) the timers
class _Recorder:
    def __init__(self):
        self.names = []

    def span(self, name):
        self.names.append(name)
        return contextlib.nullcontext()


def test_measure_times_parent_and_children():
    rec = _Recorder()
    t = PhaseTimers(tracer=rec)

    def slow(x):
        time.sleep(0.01)
        return x

    for i in range(3):
        assert t.measure("round_step", slow, i) == i
    with t.phase("host_batch_plan"):
        pass
    assert set(t.totals) == {"round_step", "round_dispatch", "round_wait",
                             "host_batch_plan"}
    assert t.counts["round_step"] == t.counts["round_dispatch"] \
        == t.counts["round_wait"] == 3
    children = t.totals["round_dispatch"] + t.totals["round_wait"]
    assert 0.03 <= t.totals["round_dispatch"] <= children \
        <= t.totals["round_step"]
    # the tracer hook is still fed, parent before its children
    assert rec.names == ["round_step", "round_dispatch", "round_wait"] * 3 \
        + ["host_batch_plan"]


def test_timers_keep_the_longest_span():
    t = PhaseTimers()
    for dt in (0.001, 0.05, 0.002):
        t.add("round_wait", dt)
    s = t.summary()["round_wait"]
    assert s["count"] == 3 and s["max_s"] == 0.05
    assert s["mean_s"] == pytest.approx(0.053 / 3, abs=1e-5)
    head, row = t.report().splitlines()
    assert head.split() == ["phase", "total_s", "count", "mean_s", "max_s"]
    assert row.split() == ["round_wait", "0.053", "3", "0.01767", "0.05000"]


# --------------------------------------------- (b) one capture on the CPU mesh
@pytest.fixture(scope="module")
def capture(tmp_path_factory):
    """ONE profiler capture: two rounds of a tiny gossip trainer, then
    two of a tiny federated one (both warmed up first)."""
    from jax.profiler import ProfileData

    trainers = [GossipTrainer(_gossip_cfg()), FederatedTrainer(_fed_cfg(0.5))]
    for tr in trainers:
        tr.run(rounds=1)
    out = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(out))
    try:
        for tr in trainers:
            tr.run(rounds=2)
    finally:
        jax.profiler.stop_trace()
    files = sorted(out.rglob("*.xplane.pb"))
    assert len(files) == 1
    profile = ProfileData.from_file(str(files[0]))
    events, ops = [], 0
    for plane in profile.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name in SPANS or ev.name == ROUND_STEP:
                    events.append((ev.name, ev.start_ns,
                                   ev.start_ns + ev.duration_ns,
                                   dict(ev.stats)))
                elif "dot" in ev.name or "fusion" in ev.name:
                    ops += 1
    return sorted(events, key=lambda e: e[1]), ops


def test_capture_has_one_step_per_round(capture):
    events, _ = capture
    steps = [e for e in events if e[0] == ROUND_STEP]
    # round 0 was the warm-up: each trainer's traced rounds are 1 and 2
    assert [e[3]["step_num"] for e in steps] == [1, 2, 1, 2]
    for a, b in zip(steps, steps[1:]):
        assert a[2] <= b[1]                     # steps do not overlap


def test_capture_nests_the_span_tree(capture):
    events, ops = capture
    assert ops > 0      # the executed ops are in the same file
    steps = [e for e in events if e[0] == ROUND_STEP]
    for _, s0, s1, _ in steps:
        inside = [e for e in events
                  if e[0] != ROUND_STEP and s0 <= e[1] and e[2] <= s1]
        # every span once a round, in the order the loop opens them
        assert [e[0] for e in inside] == list(SPANS)
        span = {e[0]: e for e in inside}
        parent = span["round_step"]
        for child in ("round_dispatch", "round_wait"):
            assert parent[1] <= span[child][1] and span[child][2] <= parent[2]
        assert span["round_dispatch"][2] <= span["round_wait"][1]
        for a, b in (("host_batch_plan", "round_step"),
                     ("round_step", "round_fetch"),
                     ("round_fetch", "round_record")):
            assert span[a][2] <= span[b][1]
    # no span of the tree outside a step
    assert len(events) == len(steps) * (len(SPANS) + 1)


# --------------------------------- (c) the scopes in the compiled round program
def _gossip_round():
    return GossipTrainer(_gossip_cfg(local_holdout=0.25), eval_every=1)


def _fed_round():
    return FederatedTrainer(_fed_cfg(1.0))


def _fed_compact():
    return FederatedTrainer(_fed_cfg(0.5, mesh_devices=1))


@pytest.mark.parametrize("build, fn_name, nested_eval", [
    (_gossip_round, "round_fn", True),
    (_fed_round, "round_fn", False),
    (_fed_compact, "compact_fn", False),
], ids=["gossip-round_fn", "federated-round_fn", "federated-compact_fn"])
def test_compiled_round_carries_the_scopes(build, fn_name, nested_eval):
    name, lowered = build().lower_round()
    assert name == fn_name
    # The persistent cache's key ignores metadata: an executable cached
    # by another version of this tree would come back with ITS scopes.
    with enable_compilation_cache(False):
        text = lowered.compile().as_text()
    stacks = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in ("dopt_local", "dopt_batch", "dopt_update", "dopt_eval",
                  "dopt_mix"):
        assert any(scope in s for s in stacks), scope
    # the update sits inside the local phase ...
    assert all("dopt_local" in s for s in stacks if "dopt_update" in s)
    # ... and no op of the mix claims to be local training or eval
    assert not [s for s in stacks if "dopt_mix" in s
                and ("dopt_local" in s or "dopt_eval" in s)]
    both = [s for s in stacks if "dopt_local" in s and "dopt_eval" in s]
    if nested_eval:
        # the holdout's per-epoch eval runs INSIDE the local phase; the
        # fleet eval under lax.cond does not
        assert both
        assert any("dopt_eval" in s and "dopt_local" not in s
                   for s in stacks)
    else:
        assert not both


def test_compiled_model1_round_carries_the_pool_scope():
    """``dopt_pool`` marks the differentiated max-pool's forward and
    backward inside the local phase; evaluation pools without it."""
    cfg = _gossip_cfg(num_users=4, synthetic_train_size=64,
                      synthetic_test_size=16, local_holdout=0.25)
    cfg = dataclasses.replace(
        cfg, model=ModelConfig(model="model1", input_shape=(28, 28, 1)),
        gossip=dataclasses.replace(cfg.gossip, local_ep=1, local_bs=8))
    _, lowered = GossipTrainer(cfg, eval_every=1).lower_round()
    with enable_compilation_cache(False):
        text = lowered.compile().as_text()
    pool = {s for s in re.findall(r'op_name="([^"]*)"', text)
            if "dopt_pool" in s}
    assert any("/jvp(dopt_pool)/reduce" in s for s in pool)
    assert any("transpose(jvp(dopt_pool))" in s for s in pool)
    placed = {s for s in pool if s.startswith("jit(")}
    assert placed and all("dopt_local" in s for s in placed)
    assert not [s for s in pool if "dopt_eval" in s]


def _model1_round_text(num_users):
    """Compiled HLO text of a one-device Model1 gossip round with a
    holdout: training steps of 128 rows, the holdout's eval (48 rows a
    worker) and the fleet eval."""
    cfg = _gossip_cfg(num_users=num_users, local_holdout=0.25,
                      synthetic_train_size=192 * num_users,
                      synthetic_test_size=16)
    cfg = dataclasses.replace(
        cfg, mesh_devices=1,
        model=ModelConfig(model="model1", input_shape=(28, 28, 1)),
        gossip=dataclasses.replace(cfg.gossip, local_ep=1, local_bs=128))
    _, lowered = GossipTrainer(cfg, eval_every=1).lower_round()
    with enable_compilation_cache(False):
        return lowered.compile().as_text()


@pytest.mark.parametrize("num_users, groups", [(8, 2), (6, 6)],
                         ids=["fleet8-packed", "fleet6-grouped"])
def test_compiled_model1_round_carries_the_conv1_scope(num_users, groups):
    """``dopt_conv1`` marks the first convolution with its bias add:
    forward and backward inside the local phase, forward in every
    evaluation.  A training step of 128 rows packs a fleet of 8 four
    workers a group; a fleet of 6 keeps one group a worker, as does the
    narrow fleet's evaluation, whose batch is not whole lane tiles."""
    conv1 = [(stack, line) for line in _model1_round_text(
        num_users).splitlines()
        for stack in re.findall(r'op_name="([^"]*dopt_conv1[^"]*)"', line)]
    stacks = {s for s, _ in conv1 if s.startswith("jit(")}
    assert any("dopt_local" in s and "transpose(jvp(dopt_conv1))" in s
               for s in stacks)
    assert all("dopt_local" in s or "dopt_eval" in s for s in stacks)

    def group_counts(tail):
        return {int(n) for s, line in conv1 if s.endswith(tail)
                for n in re.findall(r"feature_group_count=(\d+)", line)}

    assert group_counts("/jvp(dopt_conv1)/conv_general_dilated") == {groups}
    assert group_counts("/dopt_conv1/conv_general_dilated") == {num_users}
    assert {"dopt_eval" in s for s in stacks
            if s.endswith("/dopt_conv1/conv_general_dilated")} == {True}


def test_resnet_round_carries_no_conv1_scope():
    """The ResNet's first convolution (three input channels a worker) is
    not the reference CNNs' conv1: its program is the parent's."""
    cfg = _gossip_cfg(num_users=4, synthetic_train_size=64,
                      synthetic_test_size=16)
    cfg = dataclasses.replace(
        cfg, mesh_devices=1,
        model=ModelConfig(model="resnet18", stage_sizes=(1, 1),
                          faithful=False, input_shape=(8, 8, 3)),
        gossip=dataclasses.replace(cfg.gossip, local_ep=1, local_bs=8))
    _, lowered = GossipTrainer(cfg, eval_every=1).lower_round()
    text = lowered.as_text(debug_info=True)
    assert "dopt_local" in text and "dopt_conv1" not in text


def test_scopes_leave_the_fingerprints_alone():
    """Scopes are metadata: the blessed default programs do not move
    (no ``--bless``).  Own process: the registry is blessed for one
    device, the test session has eight."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    run = subprocess.run(
        [sys.executable, "-m", "dopt.analysis.fingerprint", "--strict"],
        cwd=REPO, env={**env, "JAX_PLATFORMS": "cpu"}, capture_output=True,
        text=True, timeout=300)
    assert run.returncode == 0, run.stdout[-2000:] + run.stderr[-2000:]
    assert "clean (3 program(s) checked)" in run.stdout
