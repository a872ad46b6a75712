"""Wall-clock-to-target-accuracy meter runs (BASELINE.json north-star
metric: "wall-clock to 90% test acc").

Runs baseline2 (16-worker D-SGD, CIFAR CNN) and baseline5 (32-worker
gossip ResNet-18) in throughput trim (native batch planner, fused round
blocks, eval every round) until the fleet-mean test accuracy crosses
the target or the preset's round budget runs out, then reports the
time-to-target via ``dopt.utils.metrics.time_to_target``.

Trim compute dtype is PER-PRESET and chosen by controlled experiment,
not by assumption (``TRIM_COMPUTE_DTYPE``): baseline2 runs float32 —
the r5 dtype control showed bf16 costs this corrected-head CNN ~2.7×
more rounds to target (bf16 0.355 vs f32 0.664 at round 10, identical
init/batches), which swamps bf16's 1.5× step-time win; baseline5's
GroupNorm ResNet shows no such tax and keeps bf16.  The bf16 trajectory
stays in the artifact as ``dtype_control`` (--dtype-control).

baseline2 additionally runs PAST the target to the full-oracle horizon
(``FULL_HORIZON``) so the artifact carries the same-round comparison
against the CONVERGED CPU baseline (oracle_final_acc_full, from
``scripts/oracle_full.py`` — ~95 min of single-core torch, run once and
merged from results/oracle_full_baseline2.json).  The meter itself is
unaffected: time-to-target is computed from the trajectory.

Data note: this environment has no network egress, so the runs use the
deterministic SYNTHETIC dataset at CIFAR scale — the artifact records
that explicitly.  Absolute accuracies are not comparable to real
CIFAR-10; the meter, cadence, and wall-clock accounting are exactly
what a real-data run would use (drop raw CIFAR under DOPT_DATA_DIR and
re-run).  seconds_per_round comes from steady-state blocks (the first,
compile-carrying block is excluded and reported separately).

Usage: python scripts/time_to_target.py [--target 0.9] [--quick]
       python scripts/time_to_target.py --dtype-control   # merge-only
Writes results/time_to_target.json.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from dopt.presets import TRIM_COMPUTE_DTYPE  # noqa: E402  (evidence:
# the artifact's dtype_control block and results/README.md)

# Presets that run past the target to a fixed horizon so the artifact
# can compare accuracy AT THE FULL ORACLE'S ROUND (57 oracle rounds →
# TPU acc_by_round[57] needs 58 rounds; consensus-first eval).
FULL_HORIZON = {"baseline2": 58}


def run_preset(name: str, *, target: float, quick: bool, block: int = 5,
               compute_dtype: str | None = None,
               stop_at_target: bool = True) -> dict:
    from dopt.engine import GossipTrainer
    from dopt.presets import get_preset
    from dopt.utils.metrics import time_to_target

    dtype = compute_dtype or TRIM_COMPUTE_DTYPE.get(name, "bfloat16")
    cfg = get_preset(name)
    cfg = cfg.replace(
        model=dataclasses.replace(cfg.model, compute_dtype=dtype),
        data=dataclasses.replace(cfg.data, plan_impl="native"),
    )
    if cfg.gossip is not None:
        # Sharded per-round eval: the full mode's W·|test| sample-
        # forwards cost more device time than the baseline5 training
        # round itself (3.1 of 5.5 s/round measured); the fleet-mean
        # metric the meter reads is an unbiased |test|-forward estimate.
        cfg = cfg.replace(gossip=dataclasses.replace(
            cfg.gossip, eval_mode="sharded"))
    budget = 20 if quick else cfg.gossip.rounds
    horizon = FULL_HORIZON.get(name)
    if horizon and not quick:
        # Run to the fixed horizon regardless of the target so the
        # artifact carries acc at the full-oracle round; the meter
        # reads the trajectory, so the extra rounds never distort
        # time-to-target.
        stop_at_target = False
        budget = horizon
    trainer = GossipTrainer(cfg, eval_every=1)

    # Warmup block (UNTIMED for the steady rate, but real training —
    # its rounds count toward the trajectory and the budget): carries
    # the jit compile of the fused k-round block, so every measured
    # block below is steady-state even when the target is reached (or
    # the budget exhausted) within the first measured block.
    warm_k = min(block, budget)
    t0 = time.perf_counter()
    trainer.run(rounds=warm_k, block=warm_k)
    warm_s = time.perf_counter() - t0
    done = warm_k

    block_times: list[tuple[int, float]] = []
    reached_at = None

    def _reached():
        nonlocal reached_at
        accs = [r.get("avg_test_acc") for r in trainer.history.rows]
        if any(a is not None and a >= target for a in accs):
            reached_at = next(i for i, a in enumerate(accs)
                              if a is not None and a >= target)
            return True
        return False

    if not (stop_at_target and _reached()):
        while done < budget:
            k = min(block, budget - done)
            t0 = time.perf_counter()
            trainer.run(rounds=k, block=k)
            block_times.append((k, time.perf_counter() - t0))
            done += k
            if stop_at_target and _reached():
                break

    # Snapshot the trajectory BEFORE any extra timing-only rounds so the
    # artifact's accuracy fields describe exactly the reported run.
    history_rows = list(trainer.history.rows)
    accs = [r.get("avg_test_acc") for r in history_rows
            if r.get("avg_test_acc") is not None]
    _reached()  # fill reached_at for non-stopping runs

    # Steady-state seconds/round from the measured (post-warmup) blocks.
    # If the warmup block alone reached the target, time one extra block
    # of the same k — the trajectory is already decided, we only need an
    # honest steady rate for the seconds axis (those extra rounds are
    # excluded from the snapshot above).
    if not block_times:
        t0 = time.perf_counter()
        trainer.run(rounds=warm_k, block=warm_k)
        block_times.append((warm_k, time.perf_counter() - t0))
    sec_per_round = (sum(t for _, t in block_times)
                     / sum(k for k, _ in block_times))

    meter = time_to_target(trainer.history, target=target,
                           seconds_per_round=sec_per_round)
    return {
        "preset": name,
        "model": cfg.model.model,
        "workers": cfg.data.num_users,
        "compute_dtype": dtype,
        "data": f"synthetic ({cfg.data.dataset}-scale; no egress — real "
                "data via DOPT_DATA_DIR)",
        "target_acc": target,
        "time_to_target": meter,
        "seconds_per_round_steady": round(sec_per_round, 4),
        "warmup_block_seconds_incl_compile": round(warm_s, 2),
        "rounds_run": done,
        "reached_at_round": reached_at,
        "final_acc": round(accs[-1], 4) if accs else None,
        "best_acc": round(max(accs), 4) if accs else None,
        # per-round fleet-mean test acc (eval_every=1) — lets the oracle
        # comparison read the TPU accuracy at the oracle's round index.
        "acc_by_round": [round(a, 4) for a in accs],
    }


def oracle_baseline(cfg, rounds: int) -> dict:
    """Sequential torch-CPU run of the SAME config on the SAME synthetic
    data for ``rounds`` rounds — the CPU-baseline accuracy anchor the
    north-star phrasing compares against ("matching the CPU baseline's
    final accuracy at ≥50× speedup", BASELINE.json).  Faithful to the
    reference's round structure (two-phase consensus → local update,
    ``simulators.py:136-167``); model init is torch's own seeded init
    (distributionally equivalent — bitwise init parity is the
    reference-surface oracle's job, tests/test_oracle_parity.py)."""
    import numpy as np
    import torch

    sys.path.insert(0, str(Path(__file__).resolve().parent))
    from bench_suite import _torch_model

    from dopt.data import eval_batches, load_dataset, make_batch_plan, partition
    from dopt.engine.oracle import OracleWorker, consensus
    from dopt.topology import build_mixing_matrices

    g = cfg.gossip
    w = cfg.data.num_users
    ds = load_dataset(cfg.data.dataset, data_dir=cfg.data.data_dir,
                      train_size=cfg.data.synthetic_train_size,
                      test_size=cfg.data.synthetic_test_size, seed=cfg.seed)
    _, index_matrix = partition(ds.train_y, w, iid=cfg.data.iid,
                                shards_per_user=cfg.data.shards,
                                seed=cfg.seed)
    mixing = build_mixing_matrices(g.topology, g.mode, w, seed=cfg.seed)

    def nchw(x):
        return (np.ascontiguousarray(np.moveaxis(x, -1, -3))
                if x.ndim >= 4 else x)

    torch.manual_seed(cfg.seed)
    proto = _torch_model(cfg.model, cfg.model.input_shape)
    init = {k: v.clone() for k, v in proto.state_dict().items()}
    workers = []
    for _ in range(w):
        m = _torch_model(cfg.model, cfg.model.input_shape)
        m.load_state_dict({k: v.clone() for k, v in init.items()})
        workers.append(OracleWorker(m, lr=cfg.optim.lr,
                                    momentum=cfg.optim.momentum))

    t_start = time.perf_counter()
    for t in range(rounds):
        w_t = mixing.for_round(t)
        states = [wk.state() for wk in workers]
        new = [consensus([(float(w_t[i, j]), states[j])
                          for j in range(w) if w_t[i, j] > 0])
               for i in range(w)]
        for wk, st in zip(workers, new):
            wk.load(st)
        plan = make_batch_plan(index_matrix, batch_size=g.local_bs,
                               local_ep=g.local_ep, seed=cfg.seed,
                               round_idx=t, impl="numpy")
        bx = nchw(ds.train_x[plan.idx])
        by = ds.train_y[plan.idx]
        for i in range(w):
            workers[i].local_update(bx[i], by[i], plan.weight[i])
    # One more consensus sweep (round `rounds`' mixing) before the final
    # eval: the TPU engine's history row k is evaluated consensus-first
    # (round order consensus → eval → local, gossip.py block_fn), so the
    # comparable TPU number is acc_by_round[rounds] and this eval must
    # sit at the same trajectory position — k local updates + the
    # (k+1)-th consensus.
    w_t = mixing.for_round(rounds)
    states = [wk.state() for wk in workers]
    new = [consensus([(float(w_t[i, j]), states[j])
                      for j in range(w) if w_t[i, j] > 0])
           for i in range(w)]
    for wk, st in zip(workers, new):
        wk.load(st)
    ex, ey, ew = eval_batches(ds.test_x, ds.test_y, batch_size=256)
    exn = nchw(ex)
    accs = [wk.inference(exn, ey, ew)[0] for wk in workers]
    return {"oracle_rounds": rounds,
            "oracle_final_acc": round(float(np.mean(accs)), 4),
            "oracle_seconds": round(time.perf_counter() - t_start, 1)}


# Oracle (sequential torch-CPU) round caps: the comparison runs the
# oracle for min(rounds the TPU run needed, cap) rounds and compares
# fleet-mean accuracy AT THE SAME ROUND INDEX — apples-to-apples on
# trajectory position.  baseline5's ResNet-18 round costs minutes of
# CPU, hence the tighter cap (the truncation is recorded in the
# artifact; baseline2's FULL oracle is the separate oracle_full.py
# payload merged below).
ORACLE_CAPS = {"baseline2": 10, "baseline5": 2}

FULL_ORACLE_PAYLOAD = Path("results/oracle_full_baseline2.json")


def merge_full_oracle(row: dict) -> None:
    """Attach the full-horizon oracle payload (oracle_full.py) and the
    same-round TPU comparison to a baseline2 result row."""
    if row["preset"] != "baseline2" or not FULL_ORACLE_PAYLOAD.exists():
        return
    payload = json.loads(FULL_ORACLE_PAYLOAD.read_text())
    row.update({k: v for k, v in payload.items() if k != "preset"})
    k = payload["oracle_rounds_full"]
    acc = row.get("acc_by_round", [])
    row["tpu_acc_at_full_oracle_round"] = acc[k] if len(acc) > k else None
    if len(acc) <= k:
        print(f"warning: TPU trajectory has {len(acc)} rounds <= full "
              f"oracle horizon {k}; same-round comparison unavailable",
              file=sys.stderr)
    fa = row.get("final_acc")
    # A row whose run never reached a final eval carries final_acc=None
    # — write an explicit null delta instead of crashing the merge.
    row["tpu_final_minus_full_oracle"] = (
        round(fa - payload["oracle_final_acc_full"], 4)
        if fa is not None else None)


def add_dtype_control(out_path: Path, *, target: float, quick: bool,
                      preset: str = "baseline2",
                      dtype: str = "bfloat16") -> None:
    """Run ``preset`` once with the OTHER compute dtype over the full
    horizon and merge the trajectory into the existing artifact as the
    single-variable dtype control: same engine, same batch planner,
    same init and batch order — only the compute dtype differs.
    Settles whether per-round convergence differences are a dtype tax
    or an init/batch-order artifact (VERDICT r4)."""
    r = run_preset(preset, target=target, quick=quick,
                   compute_dtype=dtype, stop_at_target=False)
    ttt = json.loads(out_path.read_text())
    for row in ttt["results"]:
        if row["preset"] == preset:
            acc = r["acc_by_round"]
            row["dtype_control"] = {
                "compute_dtype": dtype,
                "seconds_per_round_steady": r["seconds_per_round_steady"],
                "rounds_run": r["rounds_run"],
                "reached_at_round": r["reached_at_round"],
                "final_acc": r["final_acc"],
                "best_acc": r["best_acc"],
                "acc_by_round": acc,
            }
            for key, k in [("control_acc_at_oracle_round",
                            row.get("oracle_rounds")),
                           ("control_acc_at_full_oracle_round",
                            row.get("oracle_rounds_full"))]:
                row[key] = (acc[k] if k is not None and len(acc) > k
                            else None)
    out_path.write_text(json.dumps(ttt, indent=2) + "\n")
    print(f"merged {dtype} control into {out_path}: "
          f"final {r['final_acc']}, reached@{r['reached_at_round']}")


def main() -> int:
    from dopt.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--target", type=float, default=0.9)
    ap.add_argument("--quick", action="store_true",
                    help="cap at 20 rounds per preset (machinery check)")
    ap.add_argument("--only", nargs="*", default=None)
    ap.add_argument("--skip-oracle", action="store_true",
                    help="skip the sequential torch-CPU baseline column")
    ap.add_argument("--reuse-oracle", action="store_true",
                    help="copy the truncated-oracle column from the "
                         "existing artifact instead of re-running torch "
                         "(the oracle depends only on preset+seed, not on "
                         "the TPU trim; baseline5's column costs ~2h CPU)")
    ap.add_argument("--dtype-control", action="store_true",
                    help="run ONLY the baseline2 bf16 dtype-control and "
                         "merge it into the existing artifact")
    ap.add_argument("--out", default="results/time_to_target.json")
    args = ap.parse_args()

    if args.dtype_control:
        add_dtype_control(Path(args.out), target=args.target,
                          quick=args.quick)
        return 0

    from dopt.presets import get_preset

    names = args.only or ["baseline2", "baseline5"]
    results = [run_preset(n, target=args.target, quick=args.quick)
               for n in names]
    cached = {}
    if args.reuse_oracle and Path(args.out).exists():
        cached = {row["preset"]: row
                  for row in json.loads(Path(args.out).read_text())["results"]
                  if "oracle_final_acc" in row}
    for r in results:
        if not args.skip_oracle and r["preset"] in cached and (
                cached[r["preset"]]["oracle_rounds"] <= r["rounds_run"] - 1):
            old_row = cached[r["preset"]]
            for key in ("oracle_rounds", "oracle_final_acc",
                        "oracle_seconds"):
                r[key] = old_row[key]
            k = r["oracle_rounds"]
            r["tpu_acc_at_oracle_round"] = (
                r["acc_by_round"][k] if len(r["acc_by_round"]) > k else None)
            r["tpu_best_minus_oracle"] = round(
                r["best_acc"] - r["oracle_final_acc"], 4)
            merge_full_oracle(r)
        elif not args.skip_oracle:
            cap = ORACLE_CAPS.get(r["preset"], 5)
            # Oracle runs k rounds + the (k+1)-th consensus; the matching
            # TPU number is acc_by_round[k] (consensus-first eval), so k
            # must stay strictly below the TPU rounds run.
            orounds = max(1, min(r["rounds_run"] - 1, cap,
                                 2 if args.quick else 10**9))
            om = oracle_baseline(get_preset(r["preset"]), orounds)
            r.update(om)
            k = om["oracle_rounds"]
            tpu_at_k = (r["acc_by_round"][k]
                        if len(r["acc_by_round"]) > k else None)
            # The oracle differs from the TPU run in init (torch's own
            # seeded init) and batch order (numpy vs native planner), so
            # same-round EARLY-trajectory accuracy carries those nuisance
            # factors alongside dtype; the dtype_control block isolates
            # dtype properly.  The checkable north-star claims live in
            # tests/test_artifacts.py (best ≥ truncated oracle; final ≥
            # full oracle − 1pt on baseline2).
            r["tpu_acc_at_oracle_round"] = tpu_at_k
            r["tpu_best_minus_oracle"] = round(
                r["best_acc"] - om["oracle_final_acc"], 4)
            merge_full_oracle(r)
        m = r["time_to_target"]
        status = (f"reached at round {m['round']} "
                  f"(~{m['seconds']:.1f}s)" if m["reached"]
                  else f"not reached in {r['rounds_run']} rounds "
                       f"(best {r['best_acc']})")
        print(f"{r['preset']} [{r['compute_dtype']}]: target "
              f"{r['target_acc']} {status} "
              f"[{r['seconds_per_round_steady']*1e3:.0f} ms/round steady]"
              + (f" oracle@{r['oracle_rounds']}r={r['oracle_final_acc']}"
                 f" tpu@same={r.get('tpu_acc_at_oracle_round')}"
                 if "oracle_final_acc" in r else ""))

    import jax

    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    if args.only and out.exists():
        # Partial regeneration: replace only the re-run presets, keep
        # the rest (baseline5's truncated oracle alone costs ~2h of
        # single-core torch — never discard it incidentally).
        old = json.loads(out.read_text())["results"]
        fresh = {r["preset"]: r for r in results}
        results = [fresh.pop(r["preset"], r) for r in old]
        results += list(fresh.values())
    out.write_text(json.dumps(
        {"suite": "time_to_target", "device": str(jax.devices()[0]),
         "results": results}, indent=2) + "\n")
    print(f"wrote {out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
