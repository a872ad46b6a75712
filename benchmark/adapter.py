"""The one file of the benchmark that touches the program.

It uses only the program's public surface: the ``dopt.config``
dataclasses, ``GossipTrainer(cfg, eval_every=)`` / ``FederatedTrainer(cfg)``,
``trainer.run(rounds=)``, ``.params``, ``.theta``, ``.history.rows``,
``.timers``, ``.mesh``, ``.num_workers``, ``.mixing.for_round(t)``,
``.index_matrix``, ``.dataset``, ``.lower_round()``, ``dopt.data``'s
``holdout_split`` / ``make_batch_plan`` / ``gather_batches``, and
``enable_compile_cache()``.  One seam is not public yet (README, "Seams"):
FedAvg's client sample is re-drawn here from ``dopt.utils.prng.host_rng``
with the engine's salt, as ``scripts/oracle_trajectory.py`` does.

Every switch a cell depends on is set explicitly from the cell's two data
files, so a changed default in the program changes no cell.
"""

from __future__ import annotations

import dataclasses

import jax
import numpy as np

from dopt.config import (DataConfig, ExperimentConfig, FederatedConfig,
                         GossipConfig, ModelConfig, OptimizerConfig)
from dopt.data import gather_batches, holdout_split, make_batch_plan
from dopt.engine import FederatedTrainer, GossipTrainer
from dopt.utils.compile_cache import enable_compile_cache  # noqa: F401
from dopt.utils.prng import host_rng

# GossipTrainer evaluates when ``t % eval_every == 0``: 0 is not a value,
# so "no eval in the window" is a cadence beyond any round a run reaches.
NEVER = 10**9
# The salt of FederatedTrainer's client-sampling stream (the seam).
FEDAVG_SAMPLE_SALT = 314159

ENGINE_SECTIONS = {"gossip": GossipConfig, "federated": FederatedConfig}


def _field(value):
    """A JSON value as a dataclass field: the configs' tuples are lists."""
    return tuple(value) if isinstance(value, list) else value


def _section(cls, *sources: dict):
    """``cls(**merged)`` where a later source may not contradict an
    earlier one (a traffic file cannot loosen a configuration's
    guarantee) and keys the dataclass lacks are an error, except in the
    guarantees, which list switches of both engines."""
    names = {f.name for f in dataclasses.fields(cls)}
    merged: dict = {}
    for i, src in enumerate(sources):
        for k, v in src.items():
            if k not in names:
                if i == 0:
                    continue
                raise KeyError(f"{cls.__name__} has no field {k!r}")
            v = _field(v)
            if k in merged and merged[k] != v:
                raise ValueError(
                    f"{cls.__name__}.{k}: {v!r} contradicts the "
                    f"configuration's {merged[k]!r}")
            merged[k] = v
    return cls(**merged)


def build_config(name: str, config: dict, traffic: dict, *, seed: int,
                 chips: int) -> ExperimentConfig:
    engine = traffic["engine"]
    guard = config["guarantees"]
    sections = {engine: _section(ENGINE_SECTIONS[engine], guard,
                                 traffic[engine])}
    return ExperimentConfig(
        name=name, seed=seed,
        data=_section(DataConfig, guard, config["data"], traffic["data"]),
        model=_section(ModelConfig, guard, config["model"]),
        optim=_section(OptimizerConfig, {}, config["optim"],
                       traffic.get("optim", {})),
        faults=None, robust=None, population=None, comm=None,
        backend="jax", mesh_devices=chips, mesh_hosts=None, **sections)


def parity_config(cfg: ExperimentConfig, traffic: dict) -> ExperimentConfig:
    """The cell's job as the traffic file's ``parity`` cuts it: full model
    width and full fleet always; ``local_ep``, ``steps_per_epoch``
    (batches of data a worker), ``local_bs``, ``lr`` and ``compute_dtype``
    where the cut names them, the cell's own where it does not."""
    cut = traffic["parity"]
    engine = traffic["engine"]
    sec = getattr(cfg, engine)
    # The batch is cut where the float32 job would not fit the chip; the
    # learning rate where the job's own would leave the first steps in an
    # unstable regime that amplifies rounding (the check is of plumbing
    # and form, not of the job's hyper-parameters).
    bs = min(cut.get("local_bs", sec.local_bs), sec.local_bs)
    data = cfg.data
    if "steps_per_epoch" in cut:
        rows = cut["steps_per_epoch"] * bs
        data = dataclasses.replace(
            data, synthetic_train_size=rows * data.num_users,
            synthetic_test_size=min(data.synthetic_test_size, 256))
    return cfg.replace(
        name=cfg.name + ".parity",
        data=data,
        model=dataclasses.replace(
            cfg.model,
            compute_dtype=cut.get("compute_dtype", cfg.model.compute_dtype)),
        optim=dataclasses.replace(cfg.optim, lr=cut.get("lr", cfg.optim.lr)),
        **{engine: dataclasses.replace(
            sec, local_ep=cut.get("local_ep", sec.local_ep), local_bs=bs)})


def rehearsal_config(cfg: ExperimentConfig, traffic: dict,
                     overrides: dict | None = None) -> ExperimentConfig:
    """A toy of the cell for the CPU sandbox: same engine, model family
    and switches, a fleet of ``chips``-divisible size and a few rows.
    ``overrides`` is the configuration file's ``rehearsal`` (``{"model":
    {...}, "data": {...}}``), for a model whose full width the sandbox
    cannot hold: depth, experts held, vocabulary rows, sequence length."""
    overrides = overrides or {}
    if not set(overrides) <= {"model", "data"}:
        raise KeyError(f"rehearsal overrides 'model' and 'data', not "
                       f"{sorted(set(overrides) - {'model', 'data'})}")
    engine = traffic["engine"]
    sec = getattr(cfg, engine)
    users = min(cfg.data.num_users, 4)
    bs = 8
    small = {k: {f: _field(v) for f, v in overrides.get(k, {}).items()}
             for k in ("model", "data")}
    return cfg.replace(
        data=dataclasses.replace(
            cfg.data, **{"num_users": users,
                         "synthetic_train_size": users * bs * 4,
                         "synthetic_test_size": 16, **small["data"]}),
        model=dataclasses.replace(
            cfg.model, **{"compute_dtype": "float32", **small["model"]}),
        **{engine: dataclasses.replace(sec, local_bs=bs,
                                       local_ep=min(sec.local_ep, 2))})


def build_trainer(cfg: ExperimentConfig, traffic: dict):
    if traffic["engine"] == "gossip":
        every = {"none": NEVER, "every_round": 1}[traffic["eval"]]
        return GossipTrainer(cfg, eval_every=every)
    return FederatedTrainer(cfg, eval_train=traffic["eval"] == "test+train")


def initial_params(trainer, traffic: dict):
    """One worker's initial parameters on the host (every worker starts
    from the same draw; the global model for FedAvg).  Read before the
    first round: the round program donates its inputs."""
    if traffic["engine"] == "gossip":
        return jax.device_get(jax.tree.map(lambda x: x[0], trainer.params))
    return jax.device_get(trainer.theta)


def final_params(trainer, traffic: dict):
    """Gossip: the list of every worker's parameters; FedAvg: the global
    model."""
    if traffic["engine"] == "gossip":
        stacked = jax.device_get(trainer.params)
        return [jax.tree.map(lambda x, i=i: x[i], stacked)
                for i in range(trainer.num_workers)]
    return jax.device_get(trainer.theta)


def train_matrix(trainer, cfg: ExperimentConfig) -> np.ndarray:
    """[workers, rows] dataset indices each worker trains on."""
    if cfg.data.local_holdout > 0:
        return holdout_split(trainer.index_matrix,
                             fraction=cfg.data.local_holdout,
                             mode=cfg.data.holdout_mode, seed=cfg.seed)[0]
    return trainer.index_matrix


def samples_per_round(trainer, cfg: ExperimentConfig, traffic: dict) -> int:
    """Worker-samples trained (forward and backward) in one round,
    padding rows excluded."""
    rows = train_matrix(trainer, cfg).shape[1]
    if traffic["engine"] == "gossip":
        return trainer.num_workers * rows * cfg.gossip.local_ep
    sampled = max(int(cfg.federated.frac * trainer.num_workers), 1)
    return sampled * rows * cfg.federated.local_ep


def reference_rounds(trainer, cfg: ExperimentConfig, traffic: dict,
                     rounds: int) -> list[dict]:
    """What the plain reference needs to replay rounds 0..rounds-1 of a
    freshly built trainer: the engine's own batches and, per engine, the
    round's mixing matrix or sampled clients."""
    engine = traffic["engine"]
    sec = getattr(cfg, engine)
    matrix = train_matrix(trainer, cfg)
    ds = trainer.dataset
    sample_rng = host_rng(cfg.seed, FEDAVG_SAMPLE_SALT)
    out = []
    for t in range(rounds):
        entry: dict = {}
        workers = None
        if engine == "gossip":
            entry["w"] = np.asarray(trainer.mixing.for_round(t), np.float32)
        else:
            m = max(int(sec.frac * trainer.num_workers), 1)
            workers = np.sort(sample_rng.choice(
                trainer.num_workers, m, replace=False)).astype(np.int32)
            entry["sel"] = [int(c) for c in workers]
        plan = make_batch_plan(matrix, batch_size=sec.local_bs,
                               local_ep=sec.local_ep, seed=cfg.seed,
                               round_idx=t, impl=cfg.data.plan_impl,
                               workers=workers)
        entry["bx"], entry["by"], entry["bw"] = gather_batches(
            ds.train_x, ds.train_y, plan)
        out.append(entry)
    return out


def losses(trainer, traffic: dict) -> dict[int, float]:
    """round -> the trainer's own mean training loss."""
    key = traffic["loss_key"]
    return {int(r["round"]): float(r[key]) for r in trainer.history.rows
            if key in r}


def param_count(trainer) -> int:
    """Parameters of ONE worker, from the stacked state's shapes."""
    return sum(int(np.prod(x.shape[1:]))
               for x in jax.tree.leaves(trainer.params))


def compiled_round(trainer) -> tuple[str, dict]:
    """The compiled round program's HLO text and what the compiler
    reserves for it on each chip (bytes of arguments, outputs, aliased
    outputs and temporaries).  ``lower_round`` consumes the run loop's
    host draws: call it on a trainer that is done.  A cache hit."""
    _, lowered = trainer.lower_round()
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    return compiled.as_text(), {
        "argument": int(mem.argument_size_in_bytes),
        "output": int(mem.output_size_in_bytes),
        "alias": int(mem.alias_size_in_bytes),
        "temp": int(mem.temp_size_in_bytes)}
