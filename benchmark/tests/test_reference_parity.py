"""The plain reference agrees with the engines at a tiny size on the
CPU: the parity check of every cell's (configuration, engine) pair, on
the rehearsal's toy fleet.  The same code decides ``correct`` on the
chip at full width."""

import json
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
# One cell per (configuration, engine): the four-chip cell repeats the
# one-chip ResNet job and costs a second ResNet compile on the CPU.
CELLS = ["model1.gossip160-ring", "model1.fedavg128", "resnet18.gossip32.chip1"]


@pytest.mark.parametrize("name", CELLS)
def test_reference_agrees_with_the_engine(name):
    from benchmark import adapter, parity
    from benchmark.run import load_cell

    cell = load_cell(name)
    cfg = adapter.build_config(name, cell["config"], cell["traffic"],
                               seed=3, chips=1)
    cfg = adapter.rehearsal_config(cfg, cell["traffic"])
    got = parity.run(cfg, cell["config"], cell["traffic"])
    # the job really trained, and the two sides agree far below that
    assert got["moved"] > 1e-3
    assert got["ok"], got


def test_a_wrong_neighbour_fails_the_tolerance():
    """What the tolerance is for: mixing with the wrong matrix is caught."""
    import numpy as np

    from benchmark import adapter, parity, reference
    from benchmark.run import load_cell

    cell = load_cell("model1.gossip160-ring")
    traffic, config = cell["traffic"], cell["config"]
    cfg = adapter.rehearsal_config(adapter.build_config(
        "x", config, traffic, seed=3, chips=1), traffic)
    pcfg = adapter.parity_config(cfg, traffic)
    trainer = adapter.build_trainer(pcfg, traffic)
    init = adapter.initial_params(trainer, traffic)
    rounds = adapter.reference_rounds(trainer, pcfg, traffic, 2)
    trainer.run(rounds=2)
    got = adapter.final_params(trainer, traffic)
    for r in rounds:
        r["w"] = np.roll(r["w"], 1, axis=1)      # every worker's neighbours shift
    objective = reference.load_objective(config["reference"])
    want = reference.run_gossip(objective, init, rounds, lr=pcfg.optim.lr,
                                momentum=pcfg.optim.momentum)
    assert reference.max_abs_error(got, want) > 10 * parity.TOLERANCE
