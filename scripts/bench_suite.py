"""Five-config benchmark suite: TPU throughput + speedup vs the
sequential torch-CPU oracle on every BASELINE.json config.

For each preset (baseline1..baseline5):
  * TPU side — the preset's workload in throughput trim (bfloat16
    compute, native C++ batch planner, fused round blocks for gossip),
    compiled once, then a timed steady-state window → rounds/sec and
    samples/sec.  Numerics/accuracy parity is covered separately by the
    oracle-parity tests and the reference replay grid
    (scripts/replay_reference.py); this suite measures speed.
  * Oracle side — the reference's execution model: N workers stepped
    SEQUENTIALLY in one process with torch SGD (SURVEY §2: the
    reference simulates distribution by looping over clients).  We time
    ONE worker's local round on the same batch plan and extrapolate
    ×(workers stepped per round) — sequential cost is linear by
    construction, and the extrapolation ignores consensus/eval cost,
    which only makes the oracle FASTER (speedups reported are lower
    bounds).

Writes results to --out (default results/bench_suite.json) and prints
one summary line per config.  Run on an otherwise-idle machine: the
oracle numbers are host-CPU timings and concurrent load inflates them
(which would overstate the reported speedups).

Usage: python scripts/bench_suite.py [--quick] [--only baseline2 ...]
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import numpy as np


# ---------------------------------------------------------------------
# Torch counterpart models (CPU oracle timing)
# ---------------------------------------------------------------------

def _torch_model(model_cfg, input_shape):
    """A torch module matching the dopt zoo model's architecture closely
    enough for fair CPU step timing (same layer shapes and FLOPs)."""
    import torch.nn as nn

    name = model_cfg.model
    if name in ("model1", "model3"):
        from dopt.engine.oracle import torch_reference_cnn

        in_ch = input_shape[-1]
        spatial = input_shape[0]
        hidden = 512 if name == "model1" else 256
        return torch_reference_cnn(in_ch, spatial, hidden,
                                   num_classes=model_cfg.num_classes,
                                   faithful=model_cfg.faithful)
    if name == "mlp":
        flat = int(np.prod(input_shape))
        return nn.Sequential(
            nn.Flatten(), nn.Linear(flat, 200), nn.ReLU(),
            nn.Linear(200, 200), nn.ReLU(),
            nn.Linear(200, model_cfg.num_classes),
        )
    if name == "logistic":
        flat = int(np.prod(input_shape))
        return nn.Sequential(nn.Flatten(),
                             nn.Linear(flat, model_cfg.num_classes))
    if name == "resnet18":
        return _torch_resnet18(in_ch=input_shape[-1],
                               num_classes=model_cfg.num_classes)
    raise ValueError(f"no torch counterpart for model {name!r}")


def _torch_resnet18(in_ch: int = 3, num_classes: int = 10):
    """CIFAR-style ResNet-18 with GroupNorm — the torch twin of
    dopt.models.zoo.ResNet18 (same stage layout and widths)."""
    import torch.nn as nn

    def gn(c):
        return nn.GroupNorm(min(32, c), c)

    class Block(nn.Module):
        def __init__(self, cin, cout, stride):
            super().__init__()
            self.conv1 = nn.Conv2d(cin, cout, 3, stride, 1, bias=False)
            self.n1 = gn(cout)
            self.conv2 = nn.Conv2d(cout, cout, 3, 1, 1, bias=False)
            self.n2 = gn(cout)
            self.relu = nn.ReLU()
            if stride != 1 or cin != cout:
                self.short = nn.Sequential(
                    nn.Conv2d(cin, cout, 1, stride, bias=False), gn(cout))
            else:
                self.short = nn.Identity()

        def forward(self, x):
            y = self.relu(self.n1(self.conv1(x)))
            y = self.n2(self.conv2(y))
            return self.relu(y + self.short(x))

    class Net(nn.Module):
        def __init__(self):
            super().__init__()
            self.stem = nn.Sequential(
                nn.Conv2d(in_ch, 64, 3, 1, 1, bias=False), gn(64), nn.ReLU())
            layers = []
            cin = 64
            for stage, blocks in enumerate((2, 2, 2, 2)):
                cout = 64 * (2 ** stage)
                for b in range(blocks):
                    layers.append(Block(cin, cout,
                                        2 if (stage > 0 and b == 0) else 1))
                    cin = cout
            self.body = nn.Sequential(*layers)
            self.head = nn.Linear(512, num_classes)

        def forward(self, x):
            x = self.body(self.stem(x))
            return self.head(x.mean(dim=(2, 3)))

    return Net()


def oracle_round_seconds(cfg, index_matrix, dataset, *, local_ep, local_bs,
                         workers_per_round,
                         max_steps=None) -> tuple[float, int, int]:
    """Time ONE worker's local round with torch on CPU and extrapolate to
    the sequential cost of all ``workers_per_round`` workers.  Returns
    (seconds, steps actually timed, steps per worker round)."""
    from dopt.data import make_batch_plan
    from dopt.engine.oracle import OracleWorker

    model = _torch_model(cfg.model, cfg.model.input_shape)
    worker = OracleWorker(model, lr=cfg.optim.lr, momentum=cfg.optim.momentum)
    plan = make_batch_plan(index_matrix, batch_size=local_bs,
                           local_ep=local_ep, seed=cfg.seed, round_idx=0,
                           workers=np.array([0]))
    idx, weight = plan.idx[0], plan.weight[0]
    steps_timed = idx.shape[0]
    if max_steps is not None and idx.shape[0] > max_steps:
        idx, weight = idx[:max_steps], weight[:max_steps]
        steps_timed = max_steps
    bx = dataset.train_x[idx]
    if bx.ndim == 5:  # [S,B,H,W,C] image batches -> torch [S,B,C,H,W]
        bx = np.ascontiguousarray(np.transpose(bx, (0, 1, 4, 2, 3)))
    by = dataset.train_y[idx]
    steps_total = plan.idx.shape[1]

    # Warm up the TRAINING path (autograd graph construction, SGD
    # momentum-buffer allocation) so the timed window measures
    # steady-state steps — otherwise per_step is biased high and the
    # "speedups are lower bounds" guarantee breaks.
    worker.local_update(bx[:1], by[:1], weight[:1])
    t0 = time.perf_counter()
    worker.local_update(bx, by, weight)
    elapsed = time.perf_counter() - t0
    per_step = elapsed / idx.shape[0]
    return per_step * steps_total * workers_per_round, steps_timed, steps_total


# ---------------------------------------------------------------------
# TPU measurement
# ---------------------------------------------------------------------

def measure_preset(name: str, *, quick: bool, skip_oracle: bool) -> dict:
    from dopt.engine import FederatedTrainer, GossipTrainer
    from dopt.presets import get_preset

    cfg = get_preset(name)
    # Throughput trim: bf16 compute + native host planner.  Same
    # algorithm, topology, data partition, and round structure.
    from dopt.presets import TRIM_COMPUTE_DTYPE

    cfg = cfg.replace(
        model=dataclasses.replace(
            cfg.model,
            compute_dtype=TRIM_COMPUTE_DTYPE.get(name, "bfloat16")),
        data=dataclasses.replace(cfg.data, plan_impl="native"),
    )
    if cfg.gossip is not None:
        # Sharded per-round eval (see GossipConfig.eval_mode): the
        # measured window carries the per-round metric without paying
        # W·|test| sample-forwards for it.
        cfg = cfg.replace(gossip=dataclasses.replace(
            cfg.gossip, eval_mode="sharded"))
    is_gossip = cfg.gossip is not None
    g = cfg.gossip if is_gossip else cfg.federated
    # Tiny models (baseline4's 248-param logistic) get a long fused
    # window: per-scan-iteration overhead is the whole round there, so
    # a short window would time the dispatch floor's variance, not the
    # workload.
    tiny = cfg.model.model == "logistic"
    rounds = 3 if quick else (5 if cfg.model.model == "resnet18"
                              else 200 if tiny else 10)

    trainer = (GossipTrainer if is_gossip else FederatedTrainer)(cfg)
    run_kwargs = {"block": rounds}
    trainer.run(rounds=rounds, **run_kwargs)           # compile + warmup
    from dopt.utils.profiling import PhaseTimers

    trainer.timers = PhaseTimers()   # phase breakdown = measured window only
    t0 = time.perf_counter()
    trainer.run(rounds=rounds, **run_kwargs)
    elapsed = time.perf_counter() - t0
    rps = rounds / elapsed

    w = cfg.data.num_users
    part_len = trainer.index_matrix.shape[1]
    if is_gossip:
        workers_per_round = w
    else:
        workers_per_round = max(int(cfg.federated.frac * w), 1)
    samples_per_round = workers_per_round * g.local_ep * part_len
    sps = rps * samples_per_round

    # MFU accounting for EVERY config (same meter as bench.py's
    # headline): training FLOPs/sample from XLA's compiled cost
    # analysis of the zoo model — generic, no per-model tables.
    import jax

    from dopt.utils.profiling import device_peak_flops, train_flops_per_sample

    p0 = jax.tree.map(lambda x: np.asarray(x[0]),
                      jax.device_get(trainer.params))
    tfps = train_flops_per_sample(
        lambda p, x: trainer.model.apply({"params": p}, x), p0,
        cfg.model.input_shape)
    if tfps != tfps:  # NaN: backend returned no cost analysis — keep the
        peak = None   # throughput numbers, drop the FLOP-derived fields.
        flops_per_round = float("nan")
        kind, _ = device_peak_flops()
    else:
        flops_per_round = tfps * samples_per_round
        kind, peak = device_peak_flops()

    out = {
        "preset": name,
        "model": cfg.model.model,
        "params": trainer.param_count,
        "workers": w,
        "workers_per_round": workers_per_round,
        "local_ep": g.local_ep,
        "local_bs": g.local_bs,
        "rounds_measured": rounds,
        "block_rounds_used": rounds,   # all measured rounds fused in ONE
        # lax.scan jit dispatch (the dispatch-overhead killer for small
        # models — baseline4's 248-param logistic round is pure host
        # overhead without it)
        "tpu_rounds_per_sec": round(rps, 4),
        "tpu_samples_per_sec": round(sps, 1),
        "device_kind": kind,
        "compute_dtype": cfg.model.compute_dtype,
        # Measured-window phase attribution (PhaseTimers): round_step is
        # the blocking device time of the fused scan dispatch,
        # host_batch_plan the host-side planning.
        "phases": trainer.timers.summary(),
    }
    if tfps == tfps:  # not NaN
        out["train_flops_per_sample"] = round(tfps)
        out["flops_per_round"] = round(flops_per_round)
        out["model_tflops_per_sec"] = round(sps * tfps / 1e12, 3)
    if peak:
        out["mfu_vs_bf16_peak"] = round(sps * tfps / peak, 4)
    if not skip_oracle:
        # resnet18: a full 800-step round on 1 CPU core takes ~minutes;
        # 24 timed steady-state steps bound the per-step time well (the
        # extrapolation provenance is recorded in oracle_steps_timed).
        max_steps = 8 if quick else (24 if cfg.model.model == "resnet18"
                                     else None)
        oracle_s, steps_timed, steps_total = oracle_round_seconds(
            cfg, trainer.index_matrix, trainer.dataset,
            local_ep=g.local_ep, local_bs=g.local_bs,
            workers_per_round=workers_per_round, max_steps=max_steps)
        out["oracle_round_sec_extrapolated"] = round(oracle_s, 3)
        out["oracle_rounds_per_sec"] = round(1.0 / oracle_s, 5)
        # Provenance of the extrapolation: per-step time measured over
        # steps_timed of the round's steps_total steps, one worker,
        # then scaled linearly (sequential execution is linear).
        out["oracle_steps_timed"] = steps_timed
        out["oracle_steps_per_worker_round"] = steps_total
        out["speedup_vs_sequential_torch_cpu"] = round(oracle_s * rps, 1)
        # Is the ≥50× north-star bar a COMPUTE comparison for this
        # config?  Decided from utilisation, independently of whether
        # the speedup happened to reach 50: when the round runs below
        # 1% of the chip's peak (mfu), >99% of its wall-clock is
        # dispatch/latency overhead — the measured 1/rps is then the
        # framework's per-round latency FLOOR, not a compute time, and
        # any speedup ratio against it grades latency, not the compute
        # path.  At that floor, hitting 50× would need
        #   flops_per_round ≥ 50 × (1/rps) × oracle_flops_per_sec,
        # which is reported so the gap is quantified, not hand-waved.
        oracle_fps = flops_per_round / oracle_s
        if oracle_fps == oracle_fps:  # not NaN (cost analysis available)
            out["oracle_flops_per_sec"] = round(oracle_fps)
        if peak:
            latency_bound = (sps * tfps / peak) < 0.01
            out["speedup_is_compute_comparison"] = not latency_bound
            if latency_bound:
                min_flops_50x = 50.0 * (1.0 / rps) * oracle_fps
                out["min_flops_per_round_for_50x_at_this_floor"] = round(
                    min_flops_50x)
                out["note"] = (
                    "TPU round is latency-floor-bound, not compute-bound "
                    f"(mfu {sps * tfps / peak:.2e} < 1% of bf16 peak): at "
                    f"the {1e3 / rps:.2f} ms/round floor the "
                    "50x-vs-sequential-CPU bar needs >= "
                    f"{min_flops_50x:.3g} FLOP/round, this config has "
                    f"{flops_per_round:.3g} — the speedup column here "
                    "measures dispatch latency, not the compute path.")
    return out


def main() -> int:
    from dopt.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="fewer rounds / truncated oracle (CI-ish)")
    ap.add_argument("--only", nargs="*", default=None)
    ap.add_argument("--skip-oracle", action="store_true")
    ap.add_argument("--out", default="results/bench_suite.json")
    args = ap.parse_args()

    names = args.only or ["baseline1", "baseline2", "baseline3",
                          "baseline4", "baseline5"]
    results = []
    for name in names:
        r = measure_preset(name, quick=args.quick,
                           skip_oracle=args.skip_oracle)
        results.append(r)
        speed = r.get("speedup_vs_sequential_torch_cpu")
        print(f"{name}: {r['tpu_rounds_per_sec']} rounds/s "
              f"({r['tpu_samples_per_sec']:.0f} samples/s, "
              f"{r['workers']} workers, {r['params']:,} params)"
              + (f" — {speed}x vs sequential torch-CPU" if speed else ""))

    import jax

    out = Path(args.out)
    if args.only and out.exists():
        # Partial regeneration: replace only the re-run presets, keep
        # the rest (their oracle columns are expensive to recompute).
        old_rows = json.loads(out.read_text())["results"]
        fresh = {r["preset"]: r for r in results}
        results = [fresh.pop(r["preset"], r) for r in old_rows]
        results += list(fresh.values())
    payload = {
        "suite": "dopt bench_suite",
        "device": str(jax.devices()[0]),
        "quick": args.quick,
        "results": results,
    }
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(payload, indent=2) + "\n")
    print(f"wrote {out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
