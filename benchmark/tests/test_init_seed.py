"""A configuration's ``init_seed`` pins its workers' initial model, and
``--seed`` still draws the rows; its ``seed`` pins both, so ``--seed``
varies nothing; a configuration with neither draws both from ``--seed``
as the program does.  At the rehearsal's CPU sizes."""

import dataclasses

import jax
import pytest

from benchmark import adapter
from benchmark.run import load_cell

INIT_SEED = "laguna-xs2.localsgd2.t4096"
SEED = "keye-vl2.localsgd2.t8192"


def config_of(name, **changes):
    config = {**load_cell(name)["config"], **changes}
    return {k: v for k, v in config.items() if v is not None}


def trainer_at(name, seed, config, **gossip):
    cell = load_cell(name)
    traffic = cell["traffic"]
    cfg = adapter.rehearsal_config(
        adapter.build_config(name, config, traffic, seed=seed,
                             chips=cell["chips"]),
        traffic, config.get("rehearsal"))
    if gossip:
        cfg = dataclasses.replace(
            cfg, gossip=dataclasses.replace(cfg.gossip, **gossip))
    return adapter.build_trainer(cfg, traffic, config)


def bits(tree):
    return [x.tobytes() for x in jax.tree.leaves(jax.device_get(tree))]


def rows(trainer):
    return trainer.dataset.train_x.tobytes()


def test_init_seed_pins_the_model_and_the_seed_draws_the_rows():
    config = config_of(INIT_SEED)
    assert isinstance(config["init_seed"], int) and config["init_seed_why"]
    assert "seed" not in config
    a = trainer_at(INIT_SEED, 3700000011, config)
    b = trainer_at(INIT_SEED, 3700000012, config)
    assert bits(a.params) == bits(b.params)
    assert rows(a) != rows(b)
    # every worker starts from the one draw, placed as the trainer's own
    # state, with momentum still zeros
    for x, m in zip(jax.tree.leaves(a.params), jax.tree.leaves(a.momentum)):
        assert x.sharding == m.sharding and x.dtype == m.dtype
        assert (x == x[:1]).all() and not m.any()
    other = trainer_at(INIT_SEED, 3700000011,
                       {**config, "init_seed": config["init_seed"] + 1})
    assert bits(other.params) != bits(a.params)


def test_a_configuration_seed_pins_the_model_and_the_rows():
    config = config_of(SEED)
    assert isinstance(config["seed"], int) and config["seed_why"]
    assert "init_seed" not in config
    a = trainer_at(SEED, 3700000011, config)
    b = trainer_at(SEED, 3700000012, config)
    assert bits(a.params) == bits(b.params) and rows(a) == rows(b)
    assert a.cfg.seed == config["seed"]


@pytest.mark.parametrize("name", [INIT_SEED, SEED])
def test_the_pinned_draw_is_the_programs_own_at_that_seed(name):
    """``pinned_params`` at ``init_seed == --seed`` is bit for bit what
    ``GossipTrainer`` draws from ``--seed``."""
    config = config_of(name, init_seed=None, seed=None)
    own = trainer_at(name, 3700000011, config)
    pinned = trainer_at(name, 3700000011, {**config, "init_seed": 3700000011})
    assert bits(pinned.params) == bits(own.params)


@pytest.mark.parametrize("name", ["model1.gossip160-ring", INIT_SEED, SEED])
def test_without_either_key_the_seed_draws_the_model(name):
    config = config_of(name, init_seed=None, seed=None)
    a = trainer_at(name, 3700000011, config)
    b = trainer_at(name, 3700000012, config)
    assert bits(a.params) != bits(b.params)
    # the program's own draw, untouched
    own = adapter.GossipTrainer(a.cfg, eval_every=adapter.NEVER)
    assert bits(own.params) == bits(a.params)


@pytest.mark.parametrize("name, gossip", [
    ("model1.fedavg128", {}),
    (INIT_SEED, {"mixing": "async"}),
])
def test_init_seed_is_refused_where_another_copy_of_the_model_is_kept(
        name, gossip):
    config = config_of(name, init_seed=1)
    with pytest.raises(ValueError, match="init_seed"):
        trainer_at(name, 3700000011, config, **gossip)
