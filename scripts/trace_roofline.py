"""Profiler-trace-backed roofline evidence for the benchmark configs.

Captures a real XLA profiler trace (``dopt.utils.profiling.trace``) of a
steady-state fused round block, then reduces the xplane to a committed
JSON summary: per-op-category self time, the top ops, and the
device/host split.  This is the evidence layer behind the MFU numbers
in ``results/bench_suite.json`` and ``BENCH_r*.json`` — the prose
roofline claims ("activation-bandwidth-bound", "conv1 has 1 input
channel") become checkable op-level timings.

Targets: ``--preset baseline5`` (32-worker ResNet-18 gossip, the north
star) and ``--preset headline`` (bench.py's 6-worker Model1 workload).

Writes results/trace_<name>.json (the raw xplane stays out of git — it
is hundreds of KB of protobuf; the summary carries the numbers).

Usage: python scripts/trace_roofline.py --preset baseline5 [--rounds 3]
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def build_trainer(preset: str):
    from dopt.engine import FederatedTrainer, GossipTrainer

    if preset == "headline":
        import bench

        cfg = bench._config(fast=True, train_size=60_000, test_size=10_000)
    else:
        from dopt.presets import get_preset

        cfg = get_preset(preset)
        cfg = cfg.replace(
            model=dataclasses.replace(cfg.model, compute_dtype="bfloat16"),
            data=dataclasses.replace(cfg.data, plan_impl="native"),
        )
    is_gossip = cfg.gossip is not None
    trainer = (GossipTrainer if is_gossip else FederatedTrainer)(
        cfg, eval_every=10_000)   # no eval inside the traced window
    return cfg, trainer


def summarize_xplane(trace_dir: str) -> dict:
    """Reduce the captured xplane to category/op-level self times
    (shared reduction: ``dopt.utils.profiling.xplane_op_stats``)."""
    from dopt.utils.profiling import xplane_op_stats

    return xplane_op_stats(trace_dir)


def main() -> int:
    from dopt.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="baseline5",
                    help="baseline1..5 or 'headline' (bench.py workload)")
    ap.add_argument("--rounds", type=int, default=3,
                    help="rounds inside the traced fused block")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    from dopt.utils.profiling import trace

    cfg, trainer = build_trainer(args.preset)
    rounds = args.rounds
    trainer.run(rounds=rounds, block=rounds)          # compile + warmup
    import jax

    with tempfile.TemporaryDirectory(prefix="dopt-trace-") as td:
        t0 = time.perf_counter()
        with trace(td):
            trainer.run(rounds=rounds, block=rounds)
            jax.block_until_ready(trainer.params)
        elapsed = time.perf_counter() - t0
        summary = summarize_xplane(td)

    payload = {
        "preset": args.preset,
        "config_name": cfg.name,
        "model": cfg.model.model,
        "workers": cfg.data.num_users,
        "rounds_traced": rounds,
        "wall_seconds_traced": round(elapsed, 3),
        "device": str(jax.devices()[0]),
        **summary,
    }
    out = Path(args.out or f"results/trace_{args.preset}.json")
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(payload, indent=2) + "\n")
    top = payload["device_categories"][:5]
    print(f"{args.preset}: {rounds} rounds traced in {elapsed:.2f}s; "
          f"device self-time {payload['device_self_time_us']/1e6:.3f}s")
    for c in top:
        print(f"  {c['op_type']:<28s} {c['pct_of_device']:6.2f}%")
    print(f"wrote {out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
