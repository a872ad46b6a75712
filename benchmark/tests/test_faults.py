"""``correct`` comes out false when the path under the harness is broken,
and when the system computes in the precision below the one stated.

Each case drives a whole run of ``benchmark.run`` (the rehearsal's toy of
a cell: no look for a chip, everything else) with every trainer the
harness builds broken underneath, and reads ``correct`` from the result
line.  The control is the parity job with the system's compute at
bfloat16, the step below the float32 its cut states."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

CELL = "model1.gossip160-ring"


class StateUnchanged:
    """A trainer whose rounds run, report their losses, and leave the
    parameters where they were."""

    def __init__(self, inner):
        self._inner = inner
        self._frozen = None

    def __getattr__(self, name):
        return getattr(self._inner, name)

    @property
    def params(self):
        return self._inner.params if self._frozen is None else self._frozen

    def run(self, rounds):
        if self._frozen is None:
            self._frozen = jax.tree.map(jnp.copy, self._inner.params)
        return self._inner.run(rounds=rounds)


def exchange_left_out(trainer):
    """The round program mixes with the identity; ``trainer.mixing``, which
    names the round's neighbours, is as the configuration says."""
    n = trainer.num_workers     # not the trainer: a cycle would outlive ``del``
    trainer._matrix_for_round = lambda t: np.eye(n)
    return trainer


FAULTS = {"none": lambda t: t, "state_unchanged": StateUnchanged,
          "exchange_left_out": exchange_left_out}


def run_cell(monkeypatch, capsys, fault):
    from benchmark import adapter, run

    build = adapter.build_trainer
    monkeypatch.setattr(adapter, "build_trainer",
                        lambda cfg, traffic, config: FAULTS[fault](
                            build(cfg, traffic, config)))
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    rc = run.main(["--workload", CELL, "--seed", "2700000003", "--seconds",
                   "1", "--trace", "0", "--rehearse"])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_a_sound_run_is_correct(monkeypatch, capsys):
    line = run_cell(monkeypatch, capsys, "none")
    assert line["correct"] is True and line["failed"] == 0
    assert list(line)[-1] == "compared"
    parity = line["compared"]["parity_error_abs"]
    assert parity["value"] <= parity["limit"] == 5e-5


@pytest.mark.parametrize("fault", ["state_unchanged", "exchange_left_out"])
def test_a_broken_path_is_not_correct(monkeypatch, capsys, fault):
    line = run_cell(monkeypatch, capsys, fault)
    assert line["correct"] is False
    parity = line["compared"]["parity_error_abs"]
    # not a near miss: the whole movement of the job, far over the limit
    assert parity["value"] > 10 * parity["limit"]


@pytest.mark.parametrize("name", [CELL, "model1.fedavg128"])
def test_the_control_one_precision_down_is_not_correct(name):
    from benchmark import adapter, parity
    from benchmark.run import load_cell

    cell = load_cell(name)
    traffic, config = cell["traffic"], cell["config"]
    cfg = adapter.rehearsal_config(adapter.build_config(
        name, config, traffic, seed=2700000004, chips=1), traffic)
    assert traffic["parity"]["compute_dtype"] == "float32"
    sound = parity.run(cfg, config, traffic)
    control = parity.run(cfg, config, {**traffic, "parity": {
        **traffic["parity"], "compute_dtype": "bfloat16"}})
    assert sound["ok"] and not control["ok"]
    assert control["error"] > 3 * max(sound["error"], parity.TOLERANCE)
