"""Compiled-HLO bytes-on-wire probe: ``python -m dopt.analysis.comm_bytes``.

The r08 bench headline claims the bucket codec shrinks the consensus
wire by ≥4x — this CLI is where that number comes from.  It lowers the
SAME MLP gossip round program three ways via the engine's
``lower_round`` hook (the one ``_round_dispatch`` builder the real run
loop dispatches, so the measured program IS the shipped program):

* ``dense``   — ``update_sharding='off'``: the plain dense consensus
  (all_gather + [n, n] contraction at f32), the wire every mode spoke
  before the flat-bucket substrate.
* ``scatter`` — the uncompressed scatter path (reduce-scatter partial
  contractions over flat buckets).
* ``codec``   — scatter + ``CommConfig(codec='qsgd')`` with a byte
  budget priced by the lossy-link model: ``link_byte_budget`` gives one
  slab's per-round goodput under the baseline1-lossy preset's
  drop/delay rates, and the gathered wire fans (n − 1) remote slabs
  into every link per round, so the per-lane schedule must shrink by
  that fan-in factor to fit — the FusionLLM (arXiv:2410.12707) WAN
  argument, priced instead of hand-waved.

Each program's collective wire bytes come from
``dopt.parallel.collectives.hlo_collective_bytes`` over the COMPILED
HLO — per op kind and per dtype, so a compressed program shows its u8
payload + f32 scale sidecar, not a docstring claim.  The headline
``wire_compression`` is dense/codec: both legs materialise gathered
fleet buffers, so the accounting compares like with like (the
scatter leg's reduce-scatter result buffers are per-shard and NOT
comparable across op kinds — reported for transparency, never
ratioed against the gather legs).

On a 1-device mesh every collective compiles away and all counts are
honestly 0 — run under ``--devices N`` (forces
``--xla_force_host_platform_device_count`` before jax init, CPU hosts
only) or on a real multi-device backend.

Prints ONE JSON object; exit 0 on success.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

# The lossy-link preset's fault rates (dopt.presets baseline1-lossy):
# the link model that MOTIVATES compression is the one that prices it.
LOSSY_LINK = {"msg_drop": 0.15, "msg_delay": 0.2, "msg_delay_max": 2}


def comm_modes_config(mode: str, *, workers: int = 8,
                      train_size: int = 2_048, test_size: int = 512,
                      rounds: int = 8, budget_mb: float = 0.0,
                      chunk: int = 64, min_codec_bytes: int = 256,
                      faults: bool = False):
    """The r08 comm-ablation workload, one config per wire mode:
    ``dense`` | ``scatter`` | ``codec``.  MLP so the leg is feasible on
    every backend the ledger sees (the r06/r07 precedent), f32 compute
    so the dense wire is the honest 4-byte baseline the codec is
    judged against.  ``faults=True`` arms the lossy preset's crash +
    churn legs (its ``msg_*`` knobs run the per-staleness link engine —
    a different wire; here they price the byte budget instead)."""
    from dopt.config import (CommConfig, DataConfig, ExperimentConfig,
                             FaultConfig, GossipConfig, ModelConfig,
                             OptimizerConfig)

    if mode not in ("dense", "scatter", "codec"):
        raise ValueError(f"unknown comm mode {mode!r}; "
                         "one of dense|scatter|codec")
    comm = None
    if mode == "codec":
        comm = CommConfig(codec="qsgd", byte_budget_mb=budget_mb,
                          chunk=chunk, min_codec_bytes=min_codec_bytes)
    return ExperimentConfig(
        name=f"bench-comm-{mode}",
        seed=2030,
        data=DataConfig(dataset="synthetic", num_users=workers, iid=True,
                        synthetic_train_size=train_size,
                        synthetic_test_size=test_size,
                        plan_impl="native"),
        model=ModelConfig(model="mlp", faithful=False),
        optim=OptimizerConfig(lr=0.05, momentum=0.9),
        gossip=GossipConfig(
            algorithm="dsgd", topology="complete", mode="metropolis",
            rounds=rounds, local_ep=1, local_bs=128,
            update_sharding="off" if mode == "dense" else "scatter"),
        faults=(FaultConfig(crash=0.05, churn=0.02, churn_span=3)
                if faults else None),
        comm=comm,
    )


def lossy_budget_bytes(dense_bytes: int, workers: int) -> int:
    """Per-lane byte budget the codec schedule must fit under the
    lossy-link preset: one slab's goodput (``link_byte_budget``)
    divided by the gathered wire's per-link fan-in (n − 1 remote
    slabs cross every link every round)."""
    from dopt.parallel.collectives import link_byte_budget

    goodput = link_byte_budget(dense_bytes, **LOSSY_LINK)
    return max(goodput // max(workers - 1, 1), 1)


def measure_comm_bytes(*, workers: int = 8, train_size: int = 2_048,
                       test_size: int = 512, chunk: int = 64,
                       min_codec_bytes: int = 256,
                       budget_mb: float | None = None) -> dict:
    """Lower + compile the three wire modes' round programs and account
    their collective bytes.  ``budget_mb=None`` derives the codec
    budget from the lossy-link preset (``lossy_budget_bytes``).  Each
    mode gets a FRESHLY constructed trainer: ``lower_round`` consumes
    the run loop's stateful host draws."""
    import jax

    from dopt.engine import GossipTrainer
    from dopt.parallel.collectives import hlo_collective_bytes

    def build(mode, bmb=0.0):
        return GossipTrainer(
            comm_modes_config(mode, workers=workers,
                              train_size=train_size, test_size=test_size,
                              budget_mb=bmb, chunk=chunk,
                              min_codec_bytes=min_codec_bytes),
            eval_every=1 << 20)

    def wire(trainer):
        _, lowered = trainer.lower_round()
        return hlo_collective_bytes(lowered.compile().as_text())

    scatter_tr = build("scatter")
    spec = scatter_tr._scatter_spec
    dense_bytes = (spec.bounds[-1] - spec.bounds[0]) * 4
    budget = (lossy_budget_bytes(dense_bytes, workers)
              if budget_mb is None else int(budget_mb * (1 << 20)))
    codec_tr = build("codec", bmb=budget / (1 << 20))
    plan = codec_tr._codec_plan
    out = {
        "workers": workers,
        "devices": jax.device_count(),
        "platform": jax.devices()[0].platform,
        "budget_bytes": int(budget),
        "plan_kinds": list(plan.kinds),
        "plan_chunk": plan.chunk,
        "plan_dense_bytes": plan.dense_bytes,
        "plan_wire_bytes": plan.wire_bytes,
        "plan_compression": round(plan.compression, 3),
        "dense": wire(build("dense")),
        "scatter": wire(scatter_tr),
        "codec": wire(codec_tr),
    }
    out["wire_compression"] = round(
        out["dense"]["total"] / max(out["codec"]["total"], 1), 3)
    return out


def main(argv=None) -> int:
    from dopt.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser(
        prog="python -m dopt.analysis.comm_bytes",
        description="compiled-HLO bytes-on-wire of the dense / scatter "
                    "/ codec round programs (one JSON object)")
    ap.add_argument("--workers", type=int, default=8)
    ap.add_argument("--devices", type=int, default=4,
                    help="forced CPU host device count (ignored when "
                         "XLA_FLAGS already pins one or a real "
                         "multi-device backend is attached)")
    ap.add_argument("--train-size", type=int, default=2_048)
    ap.add_argument("--test-size", type=int, default=512)
    ap.add_argument("--chunk", type=int, default=64)
    ap.add_argument("--min-codec-bytes", type=int, default=256)
    ap.add_argument("--budget-mb", type=float, default=None,
                    help="codec byte budget in MiB (default: derived "
                         "from the lossy-link preset)")
    args = ap.parse_args(argv)

    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count="
            f"{args.devices}").strip()
    result = measure_comm_bytes(
        workers=args.workers, train_size=args.train_size,
        test_size=args.test_size, chunk=args.chunk,
        min_codec_bytes=args.min_codec_bytes, budget_mb=args.budget_mb)
    json.dump(result, sys.stdout)
    print()
    return 0


if __name__ == "__main__":
    sys.exit(main())
