"""Multi-round trajectory parity: TPU engine vs the faithful torch oracle.

For each config, runs N full rounds on BOTH backends from the same init,
same mixing matrices / client samples, and byte-identical batch plans,
then records the worst per-round parameter divergence.  This is the
numerics-trust artifact: the step-level oracle tests
(tests/test_oracle_parity.py) pin single steps; this script shows whole
TRAJECTORIES stay glued together across rounds on every algorithm
family the reference has.

Gossip configs replicate the reference's two-phase synchronous schedule
(simulators.py:147-165); federated configs replicate the server round
(servers.py:50-81) including partial participation, persistent client
optimizers, FedProx/FedADMM gradient edits, and dual ascent.

Writes --out (default results/oracle_trajectory.json) and prints one
line per config.  CPU-heavy (sequential torch): sizes are small.

Usage: python scripts/oracle_trajectory.py [--rounds 5]
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax
import numpy as np

from dopt.config import (DataConfig, ExperimentConfig, FederatedConfig,
                         GossipConfig, ModelConfig, OptimizerConfig)
from dopt.data import gather_batches, make_batch_plan
from dopt.engine import FederatedTrainer, GossipTrainer
from dopt.engine.oracle import (OracleWorker, consensus,
                                flax_cnn_params_to_torch, nhwc_to_nchw,
                                torch_cnn_params_to_flax, torch_reference_cnn,
                                _flatten2)
from dopt.utils.prng import host_rng

N_WORKERS = 4
LR, MOM, RHO = 0.05, 0.5, 0.1
BS, SEED = 16, 11


def _base_cfg(name: str, **kw) -> ExperimentConfig:
    return ExperimentConfig(
        name=name, seed=SEED,
        data=DataConfig(dataset="synthetic", num_users=N_WORKERS, iid=False,
                        shards=2, synthetic_train_size=128,
                        synthetic_test_size=32),
        model=ModelConfig(model="model1", input_shape=(28, 28, 1),
                          faithful=True),
        optim=OptimizerConfig(lr=LR, momentum=MOM, rho=RHO),
        **kw,
    )


def _workers(init_params, algorithm="sgd"):
    out = []
    for _ in range(N_WORKERS):
        tm = torch_reference_cnn(1, 28, 512, faithful=True)
        tm.load_state_dict(flax_cnn_params_to_torch(init_params, 28))
        out.append(OracleWorker(tm, lr=LR, momentum=MOM, rho=RHO,
                                algorithm=algorithm))
    return out


def _divergence(trainer_params, workers) -> tuple[float, float]:
    """(max absolute entry diff, global relative L2 error) across the
    fleet.  Relative L2 is the stable trajectory metric — absolute max
    lands on the largest-magnitude entries and grows with the faithful
    objective's chaotic amplification."""
    worst = 0.0
    num = den = 0.0
    final_j = jax.device_get(trainer_params)
    for i, wk in enumerate(workers):
        p_t = _flatten2(torch_cnn_params_to_flax(wk.model.state_dict(), 28))
        p_j = _flatten2(jax.tree.map(lambda x: x[i], final_j))
        for k in p_t:
            d = np.asarray(p_j[k], np.float64) - np.asarray(p_t[k], np.float64)
            worst = max(worst, float(np.abs(d).max()))
            num += float((d ** 2).sum())
            den += float((np.asarray(p_t[k], np.float64) ** 2).sum())
    return worst, float(np.sqrt(num / max(den, 1e-30)))


def gossip_trajectory(topology: str, mode: str, rounds: int,
                      local_ep: int = 1) -> dict:
    cfg = _base_cfg(
        f"traj-dsgd-{topology}-{mode}",
        gossip=GossipConfig(algorithm="dsgd", topology=topology, mode=mode,
                            rounds=rounds, local_ep=local_ep, local_bs=BS),
    )
    tr = GossipTrainer(cfg)
    init = jax.device_get(jax.tree.map(lambda x: x[0], tr.params))
    mixing, index_matrix, ds = tr.mixing, tr._train_matrix, tr.dataset
    workers = _workers(init)

    diffs = []
    for t in range(rounds):
        tr.run(rounds=1)
        w = mixing.for_round(t)
        states = [wk.state() for wk in workers]
        new = [consensus([(float(w[i, j]), states[j])
                          for j in range(N_WORKERS) if w[i, j] > 0])
               for i in range(N_WORKERS)]
        for wk, st in zip(workers, new):
            wk.load(st)
        plan = make_batch_plan(index_matrix, batch_size=BS,
                               local_ep=local_ep, seed=SEED, round_idx=t)
        bx, by, bw = gather_batches(ds.train_x, ds.train_y, plan)
        for i, wk in enumerate(workers):
            wk.local_update(nhwc_to_nchw(bx[i]), by[i], bw[i])
        diffs.append(_divergence(tr.params, workers))
    return {"config": cfg.name, "rounds": rounds,
            "max_absdiff_per_round": [round(a, 8) for a, _ in diffs],
            "rel_l2_per_round": [round(r, 8) for _, r in diffs]}


def federated_trajectory(algorithm: str, rounds: int, frac: float = 0.5,
                         cfg: ExperimentConfig | None = None) -> dict:
    cfg = cfg or _base_cfg(
        f"traj-{algorithm}",
        federated=FederatedConfig(algorithm=algorithm, frac=frac,
                                  rounds=rounds, local_ep=1, local_bs=BS),
    )
    frac = cfg.federated.frac
    local_ep = cfg.federated.local_ep
    bs = cfg.federated.local_bs
    n = cfg.data.num_users
    lr, mom, rho = cfg.optim.lr, cfg.optim.momentum, cfg.optim.rho
    tr = FederatedTrainer(cfg)
    init = jax.device_get(tr.theta)
    index_matrix, ds = tr._train_matrix, tr.dataset
    workers = []
    for _ in range(n):
        tm = torch_reference_cnn(1, 28, 512, faithful=True)
        tm.load_state_dict(flax_cnn_params_to_torch(init, 28))
        workers.append(OracleWorker(
            tm, lr=lr, momentum=mom, rho=rho,
            algorithm={"fedavg": "sgd"}.get(algorithm, algorithm)))
    import torch

    theta_t = {k: v.clone() for k, v in
               flax_cnn_params_to_torch(init, 28).items()}
    # Same sampling stream as FederatedTrainer._sample_indices.
    rng = host_rng(cfg.seed, 314159)

    diffs = []
    for t in range(rounds):
        tr.run(rounds=1)
        m = max(int(frac * n), 1)
        sel = np.sort(rng.choice(n, m, replace=False))
        plan = make_batch_plan(index_matrix, batch_size=bs,
                               local_ep=local_ep, seed=cfg.seed, round_idx=t)
        bx, by, bw = gather_batches(ds.train_x, ds.train_y, plan)
        for i in sel:
            wk = workers[i]
            wk.load(theta_t)
            needs_theta = algorithm in ("fedprox", "fedadmm")
            wk.local_update(nhwc_to_nchw(bx[i]), by[i], bw[i],
                            theta=theta_t if needs_theta else None)
            if algorithm == "fedadmm":
                wk.update_duals(theta_t)
        with torch.no_grad():
            states = [workers[i].state() for i in sel]
            theta_t = {k: sum(st[k] for st in states) / len(states)
                       for k in theta_t}
        diffs.append(_divergence(tr.params, workers))
    # Also check the global model.
    theta_flax = _flatten2(torch_cnn_params_to_flax(theta_t, 28))
    theta_j = _flatten2(jax.device_get(tr.theta))
    theta_diff = max(float(np.abs(np.asarray(theta_j[k])
                                  - np.asarray(theta_flax[k])).max())
                     for k in theta_flax)
    return {"config": cfg.name, "rounds": rounds,
            "max_absdiff_per_round": [round(a, 8) for a, _ in diffs],
            "rel_l2_per_round": [round(r, 8) for _, r in diffs],
            "final_theta_absdiff": round(theta_diff, 8)}


def reference_shaped_federated(rounds: int = 20) -> ExperimentConfig:
    """The P1 notebook config's SHAPE (20 rounds, local_ep=10,
    local_bs=50, lr=0.1, momentum=0.5, IID, deterministic 90/10 local
    holdout — cells 8/10) subsampled to 10 users / frac 0.3 so the
    sequential 1-core torch oracle stays feasible (VERDICT r1 #7)."""
    return ExperimentConfig(
        name="traj-reference-fedavg-shape", seed=SEED,
        data=DataConfig(dataset="synthetic", num_users=10, iid=True,
                        synthetic_train_size=1000, synthetic_test_size=64,
                        local_holdout=0.1, holdout_mode="deterministic"),
        model=ModelConfig(model="model1", input_shape=(28, 28, 1),
                          faithful=True),
        optim=OptimizerConfig(lr=0.1, momentum=0.5, rho=0.1),
        federated=FederatedConfig(algorithm="fedavg", frac=0.3,
                                  rounds=rounds, local_ep=10, local_bs=50),
    )


def main() -> int:
    from dopt.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--long", action="store_true",
                    help="add the long-horizon reference-shaped runs: "
                         "20-round federated (local_ep=10, bs=50, lr=0.1, "
                         "90/10 holdout) and 12-round multi-epoch gossip")
    ap.add_argument("--out", default="results/oracle_trajectory.json")
    args = ap.parse_args()

    results = []
    for topo, mode in [("circle", "stochastic"),
                       ("complete", "stochastic"),
                       ("circle", "double_stochastic"),
                       ("complete", "double_stochastic")]:
        r = gossip_trajectory(topo, mode, args.rounds)
        results.append(r)
        print(f"{r['config']}: rel_l2 {max(r['rel_l2_per_round'])}")
    for algo in ("fedavg", "fedprox", "fedadmm"):
        r = federated_trajectory(algo, args.rounds)
        results.append(r)
        print(f"{r['config']}: rel_l2 {max(r['rel_l2_per_round'])} "
              f"(theta absdiff {r['final_theta_absdiff']})")
    if args.long:
        r = gossip_trajectory("circle", "stochastic", 12, local_ep=2)
        r["config"] += "-12r-2ep"
        results.append(r)
        print(f"{r['config']}: rel_l2 {max(r['rel_l2_per_round'])}")
        r = federated_trajectory("fedavg", 20,
                                 cfg=reference_shaped_federated(20))
        results.append(r)
        print(f"{r['config']}: rel_l2 {max(r['rel_l2_per_round'])} "
              f"(theta absdiff {r['final_theta_absdiff']})")

    worst = max(max(r["rel_l2_per_round"]) for r in results)
    payload = {"suite": "oracle trajectory parity",
               "workers": N_WORKERS, "rounds": args.rounds,
               "long_horizon": args.long,
               "worst_rel_l2": worst, "results": results}
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(payload, indent=2) + "\n")
    print(f"worst relative-L2 across all configs/rounds: {worst}; wrote {out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
