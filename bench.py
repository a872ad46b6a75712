"""dopt benchmark — gossip rounds/sec on the reference's P2 workload.

Reproduces the reference's gossip experiment shape (`Weighted
Average.ipynb` cell 11: 6 workers, Model1 1.66M params, MNIST-sized
data, non-IID 2 shards/user, local_ep=4, local_bs=128, circle topology,
stochastic mixing) and measures steady-state gossip rounds per second on
the available accelerator.

Two modes are measured in one run:
  * fast      — the TPU-native configuration: bfloat16 compute, native
                C++ batch planner, all rounds fused into one lax.scan
                dispatch.  This is the headline number.
  * faithful  — float32 with the numpy (PCG64) batch planner: the
                torch-oracle-parity configuration.  Reported alongside.
Both train the identical faithful objective (double-softmax head),
algorithm, round order (consensus → eval → local epochs), data
partition, and mixing matrices.  The modes differ in compute dtype AND
in batch order (the native planner draws from its own xoshiro stream),
so the reported accuracies are a sanity check that the fast mode trains
equally well — not a controlled single-variable dtype ablation.

Baseline: the reference runs ~10 rounds in ~800s on Colab
(BASELINE.md: "Gossip throughput (derived) ~0.012 rounds/s").  Data is
synthetic at exactly MNIST scale (60,000 train / 10,000 test samples,
28x28x1) because this environment has no network egress; per-round
FLOPs and communication volume match the real workload.

What bounds MFU (~21% of bf16 peak on a v5e chip, measured): the round
is 316 dependent SGD steps (79 steps/epoch x 4 epochs) over a 768-row
effective batch (6 worker lanes x 128).  Round 4 removed the three
structural overheads (results/trace_headline.json before/after):
per-step minibatch gathers — 18% of device time, now ~1% via flat
[N, F] resident data + slab gathers; select_and_scatter maxpool
backward — 12%, replaced by a reshape-max whose VJP is an elementwise
eq-mask; and vmap-over-workers conv lowering — replaced by the grouped
stacked forward (dopt.models.make_stacked_apply), which is where most
of the 1.74 -> 2.39 rounds/s came from.  What remains is the conv
stack itself (~50% of device time): Model1's conv1 has 1 input channel
(no MXU channel contraction to amortise activation traffic) and the
faithful 5x5 convs at 28x28 are activation-heavy relative to their
FLOPs.  Levers tried and rejected: pallas fused SGD update (breaks
XLA's gradient/update fusion, 1.6x slower), bf16 param storage (+11%
throughput but -10pt accuracy), carrying grouped-layout kernels
through the scan (XLA picks worse conv layouts, +6% device time).
Eval is evaluated OUTSIDE the measured window (it is a metric, not
the workload).

Round 6: the fast leg defaults to ``update_sharding="scatter"`` (the
bucketed reduce-scatter consensus/update hot path —
arXiv:2004.13336 applied to the mixing round; ``--update-sharding off`` reverts), the wall measurement
is outlier-hardened (min/max-trimmed median + a ``--max-spread`` retry
gate — the r5 27.4% raw spread made single-window walls meaningless),
and the traced blocks additionally report the conv / mixing-comm /
update fractions of device time (named-scope attribution,
``dopt.utils.profiling.classify_phase``) so the "conv fraction" claim
is measured, not guessed.

Round 7: the client-scale legs (dopt.population) — baseline3 with a
1k- and a 10k-client population registry, cohort-sampled onto the 16
lanes in waves with hierarchical (bucketed reduce-scatter)
aggregation.  Each leg prints its own JSON line with the
``clients_per_sec`` headline (cohort · rounds/sec — client visits
served per second) plus ``population``/``cohort_size``/``waves``
fields; ``--quick`` emits the 1k line as a CI artifact.

r07: the fused mix+update epilogue lands in the engines.  ``--fused on``
(default) measures the fast workload twice — ``fused_update`` off vs on,
both ``update_sharding='off'`` — and folds ``fused_rounds_per_sec`` +
``fused_speedup`` into the headline line and the ``--quick`` artifact
(CI asserts present-and-finite).  ``--hbm-reuse-check`` is the donation
proof: the fused workload at block=1 vs block=4, peak-memory gauge flat
to ±10% or nonzero exit.  The seqlm workload is promoted to a headline
leg (``scripts/bench_seqlm.py`` stays the standalone sweep tool): its
tokens/sec line rides every full run and appends to the ledger under
its own ``(seqlm_tokens_per_sec, device_kind)`` key.  ``--fused-modes``
is the standalone r07 mode (the r06 ``--topology-modes`` pattern): the
fused A/B on the backend-portable MLP gossip workload with the
hbm-reuse proof folded in, plus the seqlm leg, each appended under its
own ledger key.

Prints the main JSON line:
  {"metric": "...", "value": N, "unit": "rounds/sec", "vs_baseline": N,
   "conv_fraction": f, "comm_fraction": f, "update_fraction": f,
   "fused_rounds_per_sec": N, "fused_speedup": N,
   "clients_per_sec_1k": N, "clients_per_sec_10k": N, ...}
plus one JSON line per client-scale leg and one for the seqlm leg.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REFERENCE_ROUNDS_PER_SEC = 0.012  # BASELINE.md derived gossip throughput

# Model1 training FLOPs per sample (fwd + bwd ≈ 3 × fwd), analytic:
#   conv1 28×28×32×(5·5·1)  MACs = 627,200
#   conv2 14×14×64×(5·5·32) MACs = 10,035,200
#   fc1   3136×512          MACs = 1,605,632
#   fc2   512×10            MACs = 5,120
#   fwd = 2 × 12,273,152 FLOPs = 24.55 MFLOP; ×3 ≈ 73.6 MFLOP/sample.
MODEL1_TRAIN_FLOPS_PER_SAMPLE = 3 * 2 * 12_273_152

def _device_peak_flops() -> tuple[str, float | None]:
    """(device_kind, bf16 peak) — dopt.utils.profiling.device_peak_flops."""
    from dopt.utils.profiling import device_peak_flops

    return device_peak_flops()


def _config(*, fast: bool, train_size: int, test_size: int,
            faithful_model: bool = True, update_sharding: str = "off",
            prefetch: str = "off", diagnostics: str = "off",
            fused: str = "off"):
    from dopt.config import (DataConfig, ExperimentConfig, GossipConfig,
                             ModelConfig, OptimizerConfig)

    return ExperimentConfig(
        name="bench-dsgd-mnist" + ("-fast" if fast else "-faithful")
             + ("" if faithful_model else "-idiomatic"),
        seed=2028,
        data=DataConfig(dataset="mnist", num_users=6, iid=False, shards=2,
                        synthetic_train_size=train_size,
                        synthetic_test_size=test_size,
                        plan_impl="native" if fast else "numpy"),
        model=ModelConfig(model="model1", faithful=faithful_model,
                          compute_dtype="bfloat16" if fast else "float32"),
        # The corrected-head objective has ~17x larger gradients than the
        # double-softmax it replaces, which puts the reference lr at the
        # edge of stability — bf16 rounding noise tipped whole runs into
        # 0.3-acc collapses (results/README.md).  Per-worker global-norm
        # clipping removes that on the bf16 leg ONLY: the faithful path
        # has no clipping (the reference has none), and the idiomatic
        # f32 leg stays unclipped too — it is the control showing the
        # instability is bf16-specific (f32 trains to 1.0 without clip).
        optim=OptimizerConfig(
            lr=0.01, momentum=0.5,
            clip_norm=1.0 if (fast and not faithful_model) else 0.0),
        gossip=GossipConfig(algorithm="dsgd", topology="circle",
                            mode="stochastic", rounds=10, local_ep=4,
                            local_bs=128,
                            update_sharding=update_sharding,
                            prefetch=prefetch, diagnostics=diagnostics,
                            fused_update=fused),
    )


def _chaos_config(*, train_size: int, test_size: int,
                  prefetch: str = "off", diagnostics: str = "off"):
    """The degraded-network cocktail on the headline workload:
    msg_drop (lossy links) + stragglers + Byzantine scale-lies +
    quarantine armed.  Every one of these modes used to force
    per-round execution; all of them now ride the fused blocked scan,
    and ``gossip_rounds_per_sec_chaos`` tracks that the degraded path
    stays compute-bound rather than dispatch-bound (the north-star
    regime — decentralized methods only pay off when the degraded path
    is engineered to the happy path's throughput standard)."""
    from dopt.config import (DataConfig, ExperimentConfig, FaultConfig,
                             GossipConfig, ModelConfig, OptimizerConfig,
                             RobustConfig)

    # baseline1-lossy-style workload (4-worker ring MNIST MLP): light
    # rounds, which is exactly where per-round execution was
    # dispatch-bound — the regime the fused chaos scan reclaims.  (The
    # model1 CNN legs above stay the compute-bound headline.)
    return ExperimentConfig(
        name="bench-chaos-baseline1-lossy",
        seed=2028,
        data=DataConfig(dataset="mnist", num_users=4, iid=False, shards=2,
                        synthetic_train_size=train_size,
                        synthetic_test_size=test_size,
                        plan_impl="native"),
        model=ModelConfig(model="mlp", faithful=False,
                          compute_dtype="bfloat16"),
        optim=OptimizerConfig(lr=0.05, momentum=0.5),
        gossip=GossipConfig(algorithm="dsgd", topology="circle",
                            mode="metropolis", rounds=20, local_ep=2,
                            local_bs=64, prefetch=prefetch,
                            diagnostics=diagnostics),
        faults=FaultConfig(msg_drop=0.15, straggle=0.25, straggle_frac=0.5,
                           corrupt=0.15, corrupt_mode="scale",
                           corrupt_scale=10.0),
        robust=RobustConfig(quarantine_after=3, quarantine_rounds=5),
    )


def _measure_chaos(train_size: int, test_size: int, rounds: int,
                   repeats: int, telemetry=None,
                   prefetch: str = "off",
                   diagnostics: str = "off") -> dict:
    """Chaos-cocktail throughput, both execution paths: ``blocked``
    (all measured rounds in one fused lax.scan dispatch — the path this
    PR opened to degraded modes) and ``per_round`` (one jit dispatch +
    host sync per round — what every chaos mode was pinned to before).
    The ratio is the headline: fused blocks must make chaos runs
    dispatch-free, and the traces are pinned bit-identical across the
    two paths by tests/test_fused_chaos.py, so the speedup is free."""
    # Telemetry rides BOTH legs (each as its own stream segment):
    # emission happens inside the timed window, so telemetering only
    # one leg would skew the blocked-vs-per-round speedup ratio with
    # --metrics-out — the ratio must compare like with like.
    # Diagnostics (when armed) ride BOTH legs, like telemetry: the
    # speedup ratio must compare like with like.
    blocked = _measure(_chaos_config(train_size=train_size,
                                     test_size=test_size,
                                     prefetch=prefetch,
                                     diagnostics=diagnostics),
                       rounds, rounds, repeats, telemetry=telemetry)
    per_round = _measure(_chaos_config(train_size=train_size,
                                       test_size=test_size,
                                       diagnostics=diagnostics),
                         rounds, 1, repeats, telemetry=telemetry)
    return {
        "gossip_rounds_per_sec_chaos": round(blocked["rounds_per_sec"], 4),
        "chaos_host_gap_pct": round(blocked["host_gap_pct"], 2),
        "chaos_host_batch_plan_fraction": round(
            blocked["host_batch_plan_fraction"], 4),
        "chaos_prefetch": prefetch,
        "chaos_spread_pct": round(blocked["spread_pct"], 2),
        "chaos_avg_test_acc": round(blocked["avg_test_acc"], 4),
        "chaos_per_round_rounds_per_sec": round(
            per_round["rounds_per_sec"], 4),
        "chaos_speedup_vs_per_round": round(
            blocked["rounds_per_sec"] / per_round["rounds_per_sec"], 2),
        "chaos_samples_per_sec": round(blocked["samples_per_sec"], 1),
        # Un-prefixed on purpose: the quick artifact spreads this dict
        # into its top level, and the CI gate asserts bytes_on_wire is
        # present-and-finite there.
        "bytes_on_wire": blocked["bytes_on_wire"],
    }


def _topology_config(*, topology: str, mixing: str, train_size: int,
                     test_size: int, workers: int = 32,
                     prefetch: str = "off"):
    """The round-r06 mixing-pattern ablation workload: 32 worker lanes
    (folded onto however many devices exist), MLP on synthetic data,
    ONE local epoch of light steps — communication-dominated by
    construction, so the topology/mixing delta is what the wall
    measures rather than the conv stack."""
    from dopt.config import (DataConfig, ExperimentConfig, GossipConfig,
                             ModelConfig, OptimizerConfig)

    return ExperimentConfig(
        name=f"bench-topo-{topology}-{mixing}",
        seed=2028,
        data=DataConfig(dataset="synthetic", num_users=workers, iid=True,
                        synthetic_train_size=train_size,
                        synthetic_test_size=test_size,
                        plan_impl="native"),
        model=ModelConfig(model="mlp", faithful=False,
                          compute_dtype="bfloat16"),
        optim=OptimizerConfig(lr=0.05, momentum=0.5),
        gossip=GossipConfig(algorithm="dsgd", topology=topology,
                            mode="metropolis", mixing=mixing, rounds=20,
                            local_ep=1, local_bs=64, prefetch=prefetch),
    )


def _measure_topology_modes(*, train_size: int, test_size: int,
                            rounds: int, repeats: int, workers: int = 32,
                            telemetry=None, prefetch: str = "off",
                            max_spread: float = 0.0) -> dict:
    """Dense vs one-peer vs async at n=32 — the r06 headline delta.

    Three legs of the identical workload, differing ONLY in the
    consensus wire: ``dense`` (complete graph — the all_gather + [n, n]
    contraction path), ``one_peer`` (the one-peer exponential shift
    schedule: one ppermute peer per round, same asymptotic contraction
    over a period), and ``async`` (one-peer + staleness-1 mixing, where
    round r's communication overlaps round r+1's compute).  The
    headline ``value`` is the one-peer sync leg; the speedup ratios and
    per-leg accuracies ride alongside so the regress ledger tracks both
    the throughput win and that the cheap wire still trains."""
    kind, _ = _device_peak_flops()
    legs = {}
    for name, topology, mixing in (("dense", "complete", "sync"),
                                   ("one_peer", "one_peer_exp", "sync"),
                                   ("async", "one_peer_exp", "async")):
        legs[name] = _measure(
            _topology_config(topology=topology, mixing=mixing,
                             train_size=train_size, test_size=test_size,
                             workers=workers, prefetch=prefetch),
            rounds, rounds, repeats, max_spread=max_spread,
            telemetry=telemetry)
        print(f"# topology-modes {name}: "
              f"{legs[name]['rounds_per_sec']:.4f} r/s (spread "
              f"{legs[name]['spread_pct']:.1f}%, "
              f"acc={legs[name]['avg_test_acc']:.4f})", file=sys.stderr)
    dense, one_peer, asynk = legs["dense"], legs["one_peer"], legs["async"]
    return {
        "metric": f"gossip_topology_modes_dsgd_mlp_{workers}workers",
        "value": round(one_peer["rounds_per_sec"], 4),
        "unit": "rounds/sec",
        "workers": workers,
        "rounds_per_block": rounds,
        "device_kind": kind,
        "prefetch": prefetch,
        "dense_rounds_per_sec": round(dense["rounds_per_sec"], 4),
        "one_peer_rounds_per_sec": round(one_peer["rounds_per_sec"], 4),
        "async_rounds_per_sec": round(asynk["rounds_per_sec"], 4),
        "one_peer_speedup_vs_dense": round(
            one_peer["rounds_per_sec"] / dense["rounds_per_sec"], 3),
        "async_speedup_vs_dense": round(
            asynk["rounds_per_sec"] / dense["rounds_per_sec"], 3),
        "async_speedup_vs_one_peer": round(
            asynk["rounds_per_sec"] / one_peer["rounds_per_sec"], 3),
        "dense_avg_test_acc": round(dense["avg_test_acc"], 4),
        "one_peer_avg_test_acc": round(one_peer["avg_test_acc"], 4),
        "async_avg_test_acc": round(asynk["avg_test_acc"], 4),
        "spread_pct": round(one_peer["spread_pct"], 2),
        "samples_per_sec": round(one_peer["samples_per_sec"], 1),
        "host_gap_pct": round(one_peer["host_gap_pct"], 2),
        "bytes_on_wire": one_peer["bytes_on_wire"],
        "dense_bytes_on_wire": dense["bytes_on_wire"],
    }


def _population_config(*, clients: int, cohort: int, train_size: int,
                       test_size: int, local_ep: int | None = None,
                       model: str | None = None, prefetch: str = "off"):
    """The client-scale leg: baseline3 (FedAvg, 16 non-IID MNIST
    shards, model1) with the worker==lane equation broken — a
    ``clients``-record registry sampling a ``cohort`` each round onto
    the 16 lanes in ceil(cohort/16) waves with hierarchical (bucketed
    reduce-scatter) aggregation (dopt.population).  ``model`` swaps the
    headline model1 CNN for a lighter one (the --quick CI mode runs the
    mlp — same registry/wave/reduce machinery end to end, CPU-viable
    FLOPs; the chaos quick leg set the precedent)."""
    import dataclasses

    from dopt.config import PopulationConfig
    from dopt.presets import baseline_3_fedavg_noniid

    cfg = baseline_3_fedavg_noniid()
    data = dataclasses.replace(cfg.data, synthetic_train_size=train_size,
                               synthetic_test_size=test_size,
                               plan_impl="native")
    fed = dataclasses.replace(cfg.federated, prefetch=prefetch)
    if local_ep is not None:
        fed = dataclasses.replace(fed, local_ep=local_ep)
    mdl = cfg.model
    if model is not None:
        mdl = dataclasses.replace(mdl, model=model, faithful=False)
    return dataclasses.replace(
        cfg, name=f"bench-baseline3-xclients-{clients}", data=data,
        federated=fed, model=mdl,
        population=PopulationConfig(clients=clients, cohort=cohort))


def _measure_population(*, clients: int, cohort: int, train_size: int,
                        test_size: int, rounds: int, repeats: int,
                        local_ep: int | None = None,
                        model: str | None = None, telemetry=None,
                        prefetch: str = "off") -> dict:
    """Client-scale throughput: rounds/sec of the population wave loop
    and the headline ``clients_per_sec`` = cohort · rounds/sec (how many
    client visits the trainer serves per second).  The federated engine
    evaluates the global model every round (the reference's cadence),
    so — unlike the gossip legs — eval is part of the measured round;
    the JSON notes it.  The wall reduction mirrors ``_measure``
    (min/max-trimmed median over independent blocks)."""
    import jax

    from dopt.engine.federated import FederatedTrainer

    cfg = _population_config(clients=clients, cohort=cohort,
                             train_size=train_size, test_size=test_size,
                             local_ep=local_ep, model=model,
                             prefetch=prefetch)
    trainer = FederatedTrainer(cfg, eval_train=False)
    if telemetry is not None:
        from dopt.obs import attach

        attach(trainer, telemetry, fresh=True)
    trainer.run(rounds=1)   # warmup: compiles the wave-scan round
    rps = []
    total = 0.0
    for _ in range(repeats):
        t0 = time.time()
        trainer.run(rounds=rounds)
        jax.block_until_ready(trainer.theta)
        elapsed = time.time() - t0
        total += elapsed
        rps.append(rounds / elapsed)
    med, spread, _ = _trimmed_stats(rps)
    reg = trainer._registry
    last = trainer.history.rows[-1]
    plan_s = trainer.timers.totals.get("host_batch_plan", 0.0)
    step_s = trainer.timers.totals.get("round_step", 0.0)
    plan_frac = plan_s / (plan_s + step_s) if plan_s + step_s > 0 else 0.0
    if telemetry is not None:
        # The clients/sec headline flows through the same emitter the
        # engines use, next to the population run's round events.
        telemetry.emit("gauge", round=max(trainer.round - 1, 0),
                       name=f"clients_per_sec_{clients}",
                       value=med * reg.cohort_size)
        telemetry.emit("gauge", round=max(trainer.round - 1, 0),
                       name="host_batch_plan_fraction", value=plan_frac)
    return {
        "metric": "clients_per_sec_baseline3_xclients",
        "value": round(med * reg.cohort_size, 2),
        "unit": "clients/sec",
        "clients_per_sec": round(med * reg.cohort_size, 2),
        "model": cfg.model.model,
        "population": reg.clients,
        "cohort_size": reg.cohort_size,
        "waves": reg.waves,
        "lanes": reg.lanes,
        "rounds_per_sec": round(med, 4),
        "spread_pct": round(spread, 2),
        "measured_seconds": round(total, 2),
        "prefetch": prefetch,
        "host_gap_pct": round(100.0 * plan_frac, 2),
        "host_batch_plan_fraction": round(plan_frac, 4),
        "eval_fused": True,
        "final_test_acc": round(float(last["test_acc"]), 4),
        "total_trained_rounds": trainer.round,
    }


def _trimmed_stats(values):
    """Shared with scripts/bench_seqlm.py — see
    ``dopt.utils.metrics.trimmed_stats``."""
    from dopt.utils.metrics import trimmed_stats

    return trimmed_stats(values)


def _bytes_on_wire(cfg) -> float:
    """Per-round collective bytes of ``cfg``'s compiled round program
    (``hlo_collective_bytes`` over ``lower_round``'s compiled HLO) — the
    bytes-on-wire headline every bench leg now carries.  Probed on a
    THROWAWAY trainer: ``lower_round`` consumes the run loop's stateful
    host draws, so probing the measured trainer would shift its fault /
    sampling streams.  On a 1-device mesh collectives compile away and
    the honest answer is 0.0; a probe that fails fails the run."""
    from dopt.engine import GossipTrainer
    from dopt.parallel.collectives import hlo_collective_bytes

    probe = GossipTrainer(cfg, eval_every=1 << 20)
    _, lowered = probe.lower_round()
    return float(hlo_collective_bytes(lowered.compile().as_text())["total"])


def _measure(cfg, rounds: int, block: int, repeats: int = 5,
             device_blocks: int = 0, max_spread: float = 0.0,
             max_retries: int = 2, telemetry=None):
    """Warm up (compile), then time ``repeats`` independent blocks of
    ``rounds`` rounds each and reduce via ``_trimmed_stats`` — wall
    clock on a shared host is noisier than device time, so a single
    window makes round-over-round comparisons noise-limited and
    untrimmed spreads are stall-poisoned.
    ``max_spread`` > 0 arms the retry gate: while the trimmed spread
    exceeds it (and retries remain), ``repeats`` more blocks are timed
    and the reduction re-runs over ALL samples.  Evaluation stays OUT
    of the measured loop (eval is a metric, not the workload; the
    reference times its rounds the same way).

    ``device_blocks`` > 0 additionally runs that many profiler-traced
    blocks and reports DEVICE-self-time rounds/sec — the basis host
    noise cannot reach — plus the conv/comm/update phase fractions of device time
    (``dopt.utils.profiling.phase_totals`` over the trace).

    Returns a dict: rounds/sec (trimmed median), spread_pct (trimmed)
    + spread_pct_raw, wall_retries/measured_blocks_total, post-run avg
    test acc, total measured seconds, samples/sec, total trained
    rounds, and — when traced — device_ms_per_round + device-basis
    rounds/sec + spread + phase_fractions.
    """
    import statistics

    from dopt.engine import GossipTrainer

    # eval_every > total rounds dispatched => the measured block carries
    # zero eval steps (lax.cond skips the branch's work at runtime).
    total_dispatch = rounds * (repeats * (1 + max_retries)
                               + device_blocks + 2)
    trainer = GossipTrainer(cfg, eval_every=10 * total_dispatch + 97)
    if telemetry is not None:
        # Round/fault/gauge events + host spans for every measured
        # block flow through the shared emitter (dopt.obs); `fresh`
        # starts a new stream segment for this leg.
        from dopt.obs import attach

        attach(trainer, telemetry, fresh=True)
    # Warmup: compile the fused block step for every block size the
    # measured loop will dispatch (the remainder block retraces).
    trainer.run(rounds=block, block=block)
    trained = block
    if rounds % block:
        trainer.run(rounds=rounds % block, block=block)
        trained += rounds % block
    import jax

    rps = []
    total = 0.0

    def time_blocks(n):
        nonlocal total, trained
        for _ in range(n):
            t0 = time.time()
            trainer.run(rounds=rounds, block=block)
            jax.block_until_ready(trainer.params)
            elapsed = time.time() - t0
            total += elapsed
            rps.append(rounds / elapsed)
            trained += rounds

    time_blocks(repeats)
    med, spread, _ = _trimmed_stats(rps)
    retries = 0
    while max_spread > 0 and spread > max_spread and retries < max_retries:
        # The wall number is meaningless at this spread — buy more
        # samples and re-reduce (the gate the 27.4% r5 spread demanded).
        retries += 1
        print(f"# wall spread {spread:.1f}% > {max_spread:.1f}%: retry "
              f"{retries}/{max_retries} with {repeats} more blocks",
              file=sys.stderr)
        time_blocks(repeats)
        med, spread, _ = _trimmed_stats(rps)
    samples_per_round = (trainer.num_workers * cfg.gossip.local_ep
                         * trainer._train_matrix.shape[1])
    out = {
        "rounds_per_sec": med,
        "spread_pct": spread,
        "spread_pct_raw": (100.0 * (max(rps) - min(rps))
                           / statistics.median(rps)),
        "wall_retries": retries,
        "measured_blocks_total": len(rps),
        "measured_seconds": total,
        "samples_per_sec": med * samples_per_round,
    }
    if device_blocks:
        try:
            from dopt.utils.profiling import PHASES, device_stats_of

            def one_block():
                # Count INSIDE the block: rounds trained before a
                # device_stats_of failure partway through still reflect
                # in fast_total_trained_rounds (the accuracy column's
                # denominator must match what actually ran).
                nonlocal trained
                trainer.run(rounds=rounds, block=block)
                jax.block_until_ready(trainer.params)
                trained += rounds

            dev_us, phase_us = [], {k: 0.0 for k in PHASES}
            for _ in range(device_blocks):
                stats = device_stats_of(one_block, telemetry=telemetry)
                if stats.get("warning"):
                    # Graceful profiler degrade (dopt.utils.profiling):
                    # the block still TRAINED (counted above); drop the
                    # device basis rather than report NaN medians.
                    print(f"# device-time basis degraded: "
                          f"{stats['warning']}", file=sys.stderr)
                    dev_us = []
                    break
                dev_us.append(stats["device_self_time_us"])
                ph = stats.get("device_phases", {})
                for k in PHASES:
                    phase_us[k] += float(ph.get(f"{k}_us", 0.0))
            if dev_us:
                dev_ms = statistics.median(dev_us) / 1e3 / rounds
                out["device_ms_per_round"] = dev_ms
                out["device_rounds_per_sec"] = 1e3 / dev_ms
                out["device_spread_pct"] = (100.0
                                            * (max(dev_us) - min(dev_us))
                                            / statistics.median(dev_us))
            tot_us = sum(phase_us.values())
            if tot_us > 0:
                # Conv / mixing-comm / update split of device time over
                # all traced blocks (named-scope + op-category
                # attribution, dopt.utils.profiling.classify_phase).
                out["phase_fractions"] = {
                    k: round(v / tot_us, 4) for k, v in phase_us.items()}
                if telemetry is not None:
                    telemetry.emit(
                        "phase", round=max(trainer.round - 1, 0),
                        fractions=out["phase_fractions"],
                        device_ms_per_round=out.get("device_ms_per_round"))
        except Exception as e:  # pragma: no cover - environment-dependent
            # The device-time basis needs the profiler + xprof stack;
            # its absence must not take down the wall-clock benchmark.
            print(f"# device-time basis unavailable: {e!r}",
                  file=sys.stderr)
    # Host-gap accounting (ROADMAP lever 2, the prefetch PR's measured
    # claim): how much of the wall the host pipeline costs.  Primary
    # basis: device vs wall rounds/sec (from the traced blocks); fallback when no device basis ran (--quick, smoke, a
    # degraded profiler): the host-timer estimate — the
    # host_batch_plan share of the measured phases.  Always finite.
    plan_s = trainer.timers.totals.get("host_batch_plan", 0.0)
    step_s = trainer.timers.totals.get("round_step", 0.0)
    plan_frac = plan_s / (plan_s + step_s) if plan_s + step_s > 0 else 0.0
    out["host_batch_plan_fraction"] = plan_frac
    if "device_rounds_per_sec" in out:
        out["host_gap_pct"] = 100.0 * (
            1.0 - out["rounds_per_sec"] / out["device_rounds_per_sec"])
    else:
        out["host_gap_pct"] = 100.0 * plan_frac
    if telemetry is not None:
        r = max(trainer.round - 1, 0)
        telemetry.emit("gauge", round=r, name="host_gap_pct",
                       value=float(out["host_gap_pct"]))
        telemetry.emit("gauge", round=r, name="host_batch_plan_fraction",
                       value=float(plan_frac))
    # Bytes-on-wire is a first-class column of every measured leg: the
    # compiled round program's collective bytes (0.0 on a 1-device
    # mesh, where there IS no wire).
    out["bytes_on_wire"] = _bytes_on_wire(cfg)
    if telemetry is not None:
        telemetry.emit("gauge", round=max(trainer.round - 1, 0),
                       name="bytes_on_wire", value=out["bytes_on_wire"])
    # Post-run accuracy reflects ALL rounds trained above (ADVICE r4):
    # the count is recorded so the accuracy column is interpretable.
    out["total_trained_rounds"] = trained
    out["avg_test_acc"] = float(trainer.evaluate()["acc"].mean())
    return out


def _measure_fused(*, train_size: int, test_size: int, rounds: int,
                   block: int, repeats: int, faithful_model: bool = True,
                   prefetch: str = "off", max_spread: float = 0.0,
                   telemetry=None):
    """Fused-epilogue A/B on the fast workload: the identical bf16 leg
    measured with ``GossipConfig.fused_update`` off and on, both with
    ``update_sharding='off'`` — the fused epilogue replaces the dense
    consensus contraction, and the scatter path is one of its
    documented non-compositions (the eligibility matrix row).  The off
    leg compiles the exact pre-change oracle-parity program; the on leg
    runs the one-pass ``fused_mix_update`` Pallas epilogue over the
    restructured (post-mix params, displacement) scan carry.  Returns
    both rounds/sec plus their ratio (``fused_speedup``) and the fused
    leg's accuracy — the headline fields the regress ledger tracks."""
    base = _measure(
        _config(fast=True, train_size=train_size, test_size=test_size,
                faithful_model=faithful_model, update_sharding="off",
                prefetch=prefetch, fused="off"),
        rounds, block, repeats, max_spread=max_spread, telemetry=telemetry)
    fused = _measure(
        _config(fast=True, train_size=train_size, test_size=test_size,
                faithful_model=faithful_model, update_sharding="off",
                prefetch=prefetch, fused="on"),
        rounds, block, repeats, max_spread=max_spread, telemetry=telemetry)
    return {
        "fused_rounds_per_sec": round(fused["rounds_per_sec"], 4),
        "fused_off_rounds_per_sec": round(base["rounds_per_sec"], 4),
        "fused_speedup": round(fused["rounds_per_sec"]
                               / base["rounds_per_sec"], 4),
        "fused_spread_pct": round(fused["spread_pct"], 2),
        "fused_avg_test_acc": round(fused["avg_test_acc"], 4),
    }


def _fused_modes_config(*, fused: str, train_size: int, test_size: int,
                        workers: int = 6, rounds: int = 8,
                        prefetch: str = "off"):
    """The r07 standalone fused-ablation workload: the hbm-reuse-gate
    shape (6 worker lanes, MLP, circle topology, metropolis weights)
    at ledger size.  MLP rather than model1 so the leg is feasible on
    every backend the ledger sees — model1's grouped conv stack is
    accelerator-bound, and the fused epilogue's cost model (one pass
    over the params instead of mix-then-axpy) is architecture-agnostic;
    the model1 delta rides the full bench's ``--fused`` leg instead."""
    from dopt.config import (DataConfig, ExperimentConfig, GossipConfig,
                             ModelConfig, OptimizerConfig)

    return ExperimentConfig(
        name=f"bench-fused-{fused}",
        seed=2029,
        data=DataConfig(dataset="synthetic", num_users=workers, iid=True,
                        synthetic_train_size=train_size,
                        synthetic_test_size=test_size,
                        plan_impl="native"),
        model=ModelConfig(model="mlp", faithful=False,
                          compute_dtype="bfloat16"),
        optim=OptimizerConfig(lr=0.05, momentum=0.9),
        gossip=GossipConfig(algorithm="dsgd", topology="circle",
                            mode="metropolis", rounds=rounds,
                            local_ep=1, local_bs=128, prefetch=prefetch,
                            fused_update=fused),
    )


def _measure_fused_modes(*, train_size: int, test_size: int, rounds: int,
                         repeats: int, workers: int = 6, telemetry=None,
                         prefetch: str = "off", max_spread: float = 0.0,
                         hbm_rounds: int | None = 8) -> dict:
    """Standalone r07 mode: the fused-epilogue A/B on the MLP gossip
    workload, under its own ledger key (same pattern as the r06
    ``--topology-modes`` leg — a different workload from the model1
    headline, so the ``(metric, device_kind)`` key keeps the windows
    separate).  Two legs of the identical blocked run differing ONLY
    in ``GossipConfig.fused_update``; the headline ``value`` is the
    fused leg's rounds/sec, with the off leg and their ratio
    (``fused_speedup``) alongside.  When ``hbm_rounds`` is set the
    donation proof (block=1 vs block=4 subprocess peaks) is folded
    into the same entry, so one ledger line carries fused throughput,
    the speedup, and the HBM-reuse evidence.  The two point runs need
    the device, so they go FIRST — once this process has touched JAX it
    holds the chip and a child would fail or hang; a failed check fails
    the run."""
    hbm = None
    if hbm_rounds:
        hbm = _hbm_reuse_measure(rounds=hbm_rounds)
        if hbm["status"] == "FAIL":
            raise SystemExit(f"hbm-reuse check failed: {json.dumps(hbm)}")
    kind, _ = _device_peak_flops()
    legs = {}
    for name in ("off", "on"):
        legs[name] = _measure(
            _fused_modes_config(fused=name, train_size=train_size,
                                test_size=test_size, workers=workers,
                                rounds=rounds, prefetch=prefetch),
            rounds, rounds, repeats, max_spread=max_spread,
            telemetry=telemetry)
        print(f"# fused-modes {name}: "
              f"{legs[name]['rounds_per_sec']:.4f} r/s (spread "
              f"{legs[name]['spread_pct']:.1f}%, "
              f"acc={legs[name]['avg_test_acc']:.4f})", file=sys.stderr)
    base, fused = legs["off"], legs["on"]
    result = {
        "metric": f"gossip_fused_epilogue_dsgd_mlp_{workers}workers",
        "value": round(fused["rounds_per_sec"], 4),
        "unit": "rounds/sec",
        "workers": workers,
        "rounds_per_block": rounds,
        "device_kind": kind,
        "prefetch": prefetch,
        "fused_rounds_per_sec": round(fused["rounds_per_sec"], 4),
        "fused_off_rounds_per_sec": round(base["rounds_per_sec"], 4),
        "fused_speedup": round(fused["rounds_per_sec"]
                               / base["rounds_per_sec"], 4),
        "fused_spread_pct": round(fused["spread_pct"], 2),
        "fused_avg_test_acc": round(fused["avg_test_acc"], 4),
        "fused_off_avg_test_acc": round(base["avg_test_acc"], 4),
        "spread_pct": round(fused["spread_pct"], 2),
        "samples_per_sec": round(fused["samples_per_sec"], 1),
        "host_gap_pct": round(fused["host_gap_pct"], 2),
        "bytes_on_wire": fused["bytes_on_wire"],
    }
    if hbm is not None:
        result["hbm_reuse_status"] = hbm["status"]
        for key in ("hbm_peak_bytes_block1", "hbm_peak_bytes_block4",
                    "growth_pct", "hbm_source"):
            if key in hbm:
                result["hbm_reuse_" + key.removeprefix("hbm_")] = hbm[key]
    return result


def _measure_comm_modes(*, train_size: int, test_size: int, rounds: int,
                        repeats: int, workers: int = 8,
                        conv_rounds: int = 24, probe_devices: int = 4,
                        telemetry=None, max_spread: float = 0.0) -> dict:
    """Standalone r08 mode: the comm-substrate codec headline under its
    own ledger key (the r06/r07 standalone-workload pattern).

    Three measured bases, one entry:

    * **bytes on wire** — the compiled-HLO collective bytes of the
      dense / raw-scatter / codec round programs, probed in a
      subprocess (``python -m dopt.analysis.comm_bytes``) pinned to the
      CPU platform with a forced multi-device host mesh: it computes a
      COUNT from compiled HLO and must not reach for the chip this
      process holds (``probe_platform`` labels the result).  The
      headline ``wire_compression`` is dense/codec — gather-vs-gather,
      the fair op-kind pairing (module docstring there).
    * **throughput** — ``_measure`` on the raw-scatter and codec legs
      (identical workload, fault-free); ``value`` is the codec leg's
      rounds/sec (``compressed_rounds_per_sec`` in the regress ledger:
      the codec must not buy its bytes with a dispatch-bound round).
    * **rounds to target** — both legs re-run with the lossy preset's
      crash + churn cocktail armed (its ``msg_*`` knobs price the byte
      budget instead — they run the per-staleness link engine, a
      different wire) for ``conv_rounds`` blocked rounds; the target is
      the raw leg's final train loss × 1.02 and each leg reports the
      first round that reaches it, so the ledger shows the compression
      schedule still trains, not just that it shrinks the wire."""
    import subprocess

    from dopt.analysis.comm_bytes import comm_modes_config

    kind, _ = _device_peak_flops()
    cmd = [sys.executable, "-m", "dopt.analysis.comm_bytes",
           "--workers", str(workers), "--devices", str(probe_devices),
           "--train-size", str(train_size), "--test-size", str(test_size)]
    run = subprocess.run(cmd, capture_output=True, text=True,
                         timeout=1_200,
                         env={**os.environ, "JAX_PLATFORMS": "cpu"},
                         cwd=os.path.dirname(os.path.abspath(__file__)))
    if run.returncode != 0:
        raise SystemExit(f"comm-bytes probe rc={run.returncode}:\n"
                         f"{run.stderr[-2000:]}")
    probe = json.loads(run.stdout.strip().splitlines()[-1])
    budget = int(probe["budget_bytes"])
    budget_mb = budget / (1 << 20)

    legs = {}
    for name in ("scatter", "codec"):
        legs[name] = _measure(
            comm_modes_config(name, workers=workers,
                              train_size=train_size, test_size=test_size,
                              rounds=rounds, budget_mb=budget_mb),
            rounds, rounds, repeats, max_spread=max_spread,
            telemetry=telemetry)
        print(f"# comm-modes {name}: "
              f"{legs[name]['rounds_per_sec']:.4f} r/s (spread "
              f"{legs[name]['spread_pct']:.1f}%, "
              f"acc={legs[name]['avg_test_acc']:.4f})", file=sys.stderr)

    def _converge(mode):
        from dopt.engine import GossipTrainer

        cfg = comm_modes_config(mode, workers=workers,
                                train_size=train_size,
                                test_size=test_size, rounds=conv_rounds,
                                budget_mb=budget_mb, faults=True)
        tr = GossipTrainer(cfg, eval_every=max(conv_rounds // 2, 1))
        tr.run(rounds=conv_rounds, block=conv_rounds)
        return [float(r["avg_train_loss"]) for r in tr.history.rows]

    raw_losses = _converge("scatter")
    codec_losses = _converge("codec")
    target = raw_losses[-1] * 1.02

    def _rounds_to(losses):
        for i, v in enumerate(losses):
            if v <= target:
                return i + 1
        return len(losses)

    raw, codec = legs["scatter"], legs["codec"]
    result = {
        "metric": f"gossip_comm_codec_dsgd_mlp_{workers}workers",
        "value": round(codec["rounds_per_sec"], 4),
        "unit": "rounds/sec",
        "workers": workers,
        "rounds_per_block": rounds,
        "device_kind": kind,
        "compressed_rounds_per_sec": round(codec["rounds_per_sec"], 4),
        "raw_scatter_rounds_per_sec": round(raw["rounds_per_sec"], 4),
        "codec_overhead_pct": round(
            100.0 * (1.0 - codec["rounds_per_sec"]
                     / raw["rounds_per_sec"]), 2),
        "budget_bytes": int(budget),
        "target_avg_train_loss": round(target, 4),
        "rounds_to_target_raw": _rounds_to(raw_losses),
        "rounds_to_target_codec": _rounds_to(codec_losses),
        "raw_final_train_loss": round(raw_losses[-1], 4),
        "codec_final_train_loss": round(codec_losses[-1], 4),
        "conv_rounds": conv_rounds,
        "codec_avg_test_acc": round(codec["avg_test_acc"], 4),
        "raw_avg_test_acc": round(raw["avg_test_acc"], 4),
        "spread_pct": round(codec["spread_pct"], 2),
        "samples_per_sec": round(codec["samples_per_sec"], 1),
        "host_gap_pct": round(codec["host_gap_pct"], 2),
        "bytes_on_wire": float(probe["codec"]["total"]),
        "dense_bytes_on_wire": float(probe["dense"]["total"]),
        "scatter_bytes_on_wire": float(probe["scatter"]["total"]),
        "wire_compression": probe["wire_compression"],
        "plan_kinds": ",".join(probe["plan_kinds"]),
        "plan_compression": probe["plan_compression"],
        "probe_devices": probe["devices"],
        "probe_platform": probe["platform"],
        "codec_bytes_by_dtype": probe["codec"]["by_dtype"],
    }
    return result


def _measure_seqlm(*, steps: int, seq_len: int, batch: int, repeats: int,
                   kv_chunk: int = 0, telemetry=None):
    """The seqlm headline leg (promoted from ``scripts/bench_seqlm.py``,
    which stays the standalone sweep tool): steady-state tokens/sec of
    the ``seqlm`` preset — decoder-only TransformerLM, ring attention,
    sequence axis sharded over all devices.  Emits the standard
    bench-line schema so the ledger judges it under its OWN
    ``(seqlm_tokens_per_sec, device_kind)`` key, separate from the
    gossip headline windows."""
    import dataclasses

    import jax

    from dopt.engine import SeqLMTrainer
    from dopt.presets import get_preset

    cfg = get_preset("seqlm")
    cfg = cfg.replace(seqlm=dataclasses.replace(
        cfg.seqlm, steps=steps, seq_len=seq_len, batch=batch,
        kv_chunk=kv_chunk, log_every=max(steps // 3, 1)))
    tr = SeqLMTrainer(cfg)
    tr.run(steps=3)                       # compile + warmup
    tokens = steps * batch * seq_len
    tps, total = [], 0.0
    for _ in range(max(repeats, 1)):
        t0 = time.time()
        tr.run(steps=steps)
        jax.block_until_ready(tr.params)
        elapsed = time.time() - t0
        total += elapsed
        tps.append(tokens / elapsed)
    med, spread, _ = _trimmed_stats(tps)
    out = {
        "metric": "seqlm_tokens_per_sec",
        "value": round(med, 1),
        "unit": "tokens/sec",
        "device_kind": str(jax.devices()[0].device_kind),
        "spread_pct": round(spread, 2),
        "measured_windows": len(tps),
        "measured_seconds": round(total, 2),
        "steps_per_window": steps,
        "attn": cfg.seqlm.attn,
        "seq_len": seq_len,
        "batch": batch,
        "kv_chunk": kv_chunk,
        "mesh_devices": tr.mesh.size,
        "params": tr.param_count,
        "final_loss": round(tr.history.last()["loss"], 4),
    }
    from dopt.utils.profiling import device_memory_stats

    mem = device_memory_stats()
    if mem is not None:
        out["hbm_peak_gb"] = round(mem["peak_bytes"] / 2**30, 3)
        out["hbm_source"] = mem["source"]
    if telemetry is not None:
        from dopt.obs.events import sanitize_metrics

        telemetry.emit("bench", metrics=sanitize_metrics(out))
    return out


def _hbm_reuse_point(block: int, rounds: int) -> None:
    """(internal, spawned by ``--hbm-reuse-check``) Run the fused
    gossip workload at ONE block size in THIS process and print its
    peak-memory gauge — per-process peaks are comparable; a shared
    process would see only the running maximum."""
    from dopt.config import (DataConfig, ExperimentConfig, GossipConfig,
                             ModelConfig, OptimizerConfig)
    from dopt.engine import GossipTrainer
    from dopt.utils.profiling import device_memory_stats

    import jax

    cfg = ExperimentConfig(
        name=f"hbm-reuse-b{block}", seed=7,
        data=DataConfig(dataset="synthetic", num_users=6,
                        synthetic_train_size=1_536,
                        synthetic_test_size=256),
        model=ModelConfig(model="mlp", input_shape=(28, 28, 1),
                          faithful=False),
        optim=OptimizerConfig(lr=0.05, momentum=0.9),
        gossip=GossipConfig(algorithm="dsgd", topology="circle",
                            mode="metropolis", rounds=rounds,
                            local_ep=1, local_bs=128,
                            fused_update="on"))
    tr = GossipTrainer(cfg, eval_every=10 * rounds + 97)
    tr.run(rounds=rounds, block=block)
    jax.block_until_ready(tr.params)
    mem = device_memory_stats()
    print(json.dumps({
        "block": block,
        "hbm_peak_bytes": None if mem is None else int(mem["peak_bytes"]),
        "hbm_source": None if mem is None else mem["source"],
    }))


def _hbm_reuse_measure(*, rounds: int = 8,
                       tolerance_pct: float = 10.0) -> dict:
    """Measure the donation proof: run the fused-epilogue workload
    per-round (block=1) and blocked (block=4), each in its OWN
    subprocess (per-process peaks are comparable; a shared process
    would see only the running maximum), and compare the peak-memory
    gauges.  Returns the verdict dict — ``status`` is ``ok`` when the
    block=4 peak is flat to ±``tolerance_pct``, ``FAIL`` on growth or
    a failed point run, ``skipped`` when the backend has no gauge."""
    import subprocess

    peaks, src = {}, None
    for block in (1, 4):
        cmd = [sys.executable, __file__, "--hbm-reuse-point", str(block),
               "--rounds", str(rounds)]
        r = subprocess.run(cmd, capture_output=True, text=True, timeout=900)
        line = next((ln for ln in r.stdout.splitlines()
                     if ln.startswith("{")), None)
        if r.returncode != 0 or line is None:
            return {"check": "hbm_reuse", "status": "FAIL",
                    "reason": f"block={block} point run failed",
                    "stderr_tail": r.stderr.strip()[-400:]}
        p = json.loads(line)
        if p["hbm_peak_bytes"] is None:
            return {"check": "hbm_reuse", "status": "skipped",
                    "reason": "no memory gauge on this backend"}
        peaks[block] = int(p["hbm_peak_bytes"])
        src = p["hbm_source"]
    growth = 100.0 * (peaks[4] - peaks[1]) / peaks[1]
    return {
        "check": "hbm_reuse",
        "status": "ok" if growth <= tolerance_pct else "FAIL",
        "hbm_peak_bytes_block1": peaks[1],
        "hbm_peak_bytes_block4": peaks[4],
        "growth_pct": round(growth, 2),
        "tolerance_pct": tolerance_pct,
        "rounds": rounds,
        "hbm_source": src,
    }


def _hbm_reuse_check(*, rounds: int = 8, tolerance_pct: float = 10.0) -> int:
    """The donation proof the CI quick job asserts (hbm-reuse gate):
    peak bytes must not scale with block length.  Round-carry donation
    through the blocked ``lax.scan`` (params/momentum/displacement
    donated into each round and each block dispatch) is what keeps the
    blocked program at one resident carry; a donation regression shows
    up here as the block=4 peak growing past the gate.  Prints one
    JSON verdict line; returns a process exit code (0 flat/skipped,
    1 regressed/failed)."""
    res = _hbm_reuse_measure(rounds=rounds, tolerance_pct=tolerance_pct)
    print(json.dumps(res))
    return 0 if res["status"] in ("ok", "skipped") else 1


def main() -> None:
    from dopt.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny data / few rounds (CI smoke, not a benchmark)")
    ap.add_argument("--quick", action="store_true",
                    help="chaos-metric-only quick run (tiny data, few "
                         "rounds): prints the gossip_rounds_per_sec_chaos "
                         "JSON line and exits — the CI artifact mode")
    ap.add_argument("--skip-chaos", action="store_true",
                    help="skip the chaos-cocktail (degraded-network) leg")
    ap.add_argument("--skip-clients", action="store_true",
                    help="skip the client-scale (population registry) legs")
    ap.add_argument("--rounds", type=int, default=None)
    ap.add_argument("--block", type=int, default=None,
                    help="rounds fused per jit dispatch (default: all "
                         "measured rounds in one fused lax.scan block)")
    ap.add_argument("--skip-faithful", action="store_true",
                    help="measure only the fast (bf16) mode")
    ap.add_argument("--repeats", type=int, default=5,
                    help="independent measured blocks; the reported value "
                         "is their min/max-trimmed median (variance "
                         "hardening: single-window wall clock is noisier "
                         "than device time)")
    ap.add_argument("--max-spread", type=float, default=10.0,
                    help="wall-spread gate (%%): while the trimmed "
                         "per-block rounds/sec spread exceeds this, the "
                         "measurement retries with --repeats more blocks "
                         "(up to 2 retries); 0 disables the gate")
    ap.add_argument("--update-sharding", choices=("off", "scatter"),
                    default="scatter",
                    help="fast-leg consensus/update execution mode "
                         "(GossipConfig.update_sharding): 'scatter' runs "
                         "the bucketed reduce-scatter hot path; the "
                         "faithful f32 leg always runs 'off' (the "
                         "oracle-parity program)")
    ap.add_argument("--prefetch", choices=("on", "off"), default="on",
                    help="host-pipeline prefetch (GossipConfig/"
                         "FederatedConfig.prefetch) on the fast, chaos "
                         "and client-scale legs: block b+1's batch "
                         "plans are built + staged to device while "
                         "block b runs (dopt.data.prefetch) — the "
                         "ROADMAP lever-2 overlap; bit-identical to "
                         "'off' by construction.  The faithful f32 leg "
                         "always runs 'off' (the oracle-parity host "
                         "loop)")
    ap.add_argument("--fused", choices=("on", "off"), default="on",
                    help="measure the fused-epilogue A/B leg (the fast "
                         "workload with GossipConfig.fused_update off vs "
                         "on, both update_sharding='off'): emits "
                         "fused_rounds_per_sec + fused_speedup into the "
                         "headline JSON line and the --quick CI "
                         "artifact; 'off' skips the pair")
    ap.add_argument("--skip-seqlm", action="store_true",
                    help="skip the seqlm headline leg (ring-attention "
                         "TransformerLM tokens/sec — its own JSON line "
                         "and its own (metric, device_kind) ledger key)")
    ap.add_argument("--seqlm-steps", type=int, default=None,
                    help="seqlm leg: steps per measured window "
                         "(default 30, smoke 4)")
    ap.add_argument("--seqlm-seq-len", type=int, default=None,
                    help="seqlm leg: sequence length "
                         "(default 2048, smoke 256)")
    ap.add_argument("--hbm-reuse-check", action="store_true",
                    help="run ONLY the donation proof: fused workload "
                         "at block=1 vs block=4 (subprocess each), "
                         "assert peak memory flat to +-10%% — exits "
                         "nonzero on growth (the CI hbm-reuse gate)")
    ap.add_argument("--hbm-reuse-point", type=int, default=None,
                    metavar="BLOCK",
                    help="(internal) one --hbm-reuse-check subprocess "
                         "point: run the fused workload at this block "
                         "size and print the peak-memory gauge")
    ap.add_argument("--skip-diagnostics", action="store_true",
                    help="skip the diagnostics-overhead leg (the fast "
                         "workload re-measured with GossipConfig."
                         "diagnostics='on'; its rounds/sec and overhead "
                         "pct land in the headline JSON line — the "
                         "acceptance bar is < 5%% vs diagnostics-off)")
    ap.add_argument("--device-blocks", type=int, default=3,
                    help="profiler-traced blocks for the device-time-basis "
                         "rounds/sec (0 disables)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="stream structured telemetry (dopt.obs JSONL) "
                         "here: the measured legs' per-round events plus "
                         "'phase' (device-time fractions), "
                         "'gauge' (clients_per_sec) and a final 'bench' "
                         "event carrying the headline JSON line; "
                         "validate with 'python -m dopt.obs.check PATH'")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome-trace JSON of the measured "
                         "blocks' host spans here (dopt.obs span tracer)")
    ap.add_argument("--history-out", default="results/bench_history.jsonl",
                    metavar="PATH",
                    help="append the headline JSON line (stamped with "
                         "git sha + run id) to this perf-regression "
                         "ledger (dopt.obs.regress; compare runs with "
                         "'python -m dopt.obs.regress PATH'); '' "
                         "disables.  --quick and --smoke runs never "
                         "append (tiny-data values would poison the "
                         "trailing medians) — CI judges the quick "
                         "artifact via 'dopt.obs.regress --candidate' "
                         "instead")
    ap.add_argument("--topology-modes", action="store_true",
                    help="run ONLY the r06 mixing-pattern ablation "
                         "(dense vs one_peer_exp vs async at n=32) and "
                         "append its own headline to the history ledger")
    ap.add_argument("--skip-topology", action="store_true",
                    help="skip the topology-modes legs in the full bench")
    ap.add_argument("--fused-modes", action="store_true",
                    help="run ONLY the r07 fused-epilogue ablation "
                         "(fused_update off vs on on the MLP gossip "
                         "workload, plus the hbm-reuse donation proof "
                         "and the seqlm leg) and append their headlines "
                         "to the history ledger")
    ap.add_argument("--comm-modes", action="store_true",
                    help="run ONLY the r08 comm-substrate ablation "
                         "(raw scatter vs the budgeted bucket codec at "
                         "n=8: compiled-HLO bytes-on-wire, throughput, "
                         "and rounds-to-target under the crash/churn "
                         "cocktail) and append its headline to the "
                         "history ledger")
    ap.add_argument("--run-id", default=None,
                    help="ledger run id for the history append "
                         "(default: derived from sha + timestamp)")
    ap.add_argument("--idiomatic", action="store_true",
                    help="benchmark the idiomatic model head (post-conv "
                         "ReLUs, logit head + softmax-CE — faithful=False) "
                         "instead of the reference-faithful double-softmax "
                         "architecture; same JSON fields, metric suffixed "
                         "_idiomatic")
    args = ap.parse_args()

    if args.hbm_reuse_point is not None:
        _hbm_reuse_point(args.hbm_reuse_point, args.rounds or 8)
        return
    if args.hbm_reuse_check:
        sys.exit(_hbm_reuse_check(rounds=args.rounds or 8))

    tele = None
    if args.metrics_out or args.trace_out:
        from dopt.obs import Telemetry

        tele = (Telemetry.to_jsonl(args.metrics_out)
                if args.metrics_out else Telemetry())

    def _finish_telemetry(result: dict | None = None) -> None:
        if tele is None:
            return
        if result is not None:
            from dopt.obs.events import sanitize_metrics

            tele.emit("bench", metrics=sanitize_metrics(result))
        tele.close()
        if args.trace_out:
            tele.write_trace(args.trace_out)
            print(f"# wrote host span trace to {args.trace_out}",
                  file=sys.stderr)
        if args.metrics_out:
            print(f"# wrote telemetry stream to {args.metrics_out}",
                  file=sys.stderr)

    if args.topology_modes:
        # Standalone r06 mode: the mixing-pattern ablation only, its
        # own metric key in the ledger (the n=32 MLP wire comparison is
        # a different workload from the model1 headline, and the
        # (metric, device_kind) ledger key keeps the windows separate).
        t_rounds = args.rounds or (3 if args.smoke else 8)
        t_repeats = 2 if args.smoke else args.repeats
        tsize, esize = (4_096, 512) if args.smoke else (16_384, 2_048)
        result = _measure_topology_modes(
            train_size=tsize, test_size=esize, rounds=t_rounds,
            repeats=t_repeats, telemetry=tele, prefetch=args.prefetch,
            max_spread=0.0 if args.smoke else args.max_spread)
        print(json.dumps(result))
        if args.history_out and not args.smoke:
            try:
                from dopt.obs.regress import append_entry

                entry = append_entry(args.history_out, result,
                                     run_id=args.run_id)
                print(f"# appended run {entry['run_id']} "
                      f"(sha {entry['git_sha'] or 'unknown'}) to "
                      f"{args.history_out}", file=sys.stderr)
            except OSError as e:
                print(f"# bench history append failed: {e}",
                      file=sys.stderr)
        _finish_telemetry(result)
        return

    if args.fused_modes:
        # Standalone r07 mode: the fused-epilogue ablation + the seqlm
        # headline only, each under its own ledger key.  Mirrors the
        # r06 --topology-modes pattern — the MLP A/B workload is
        # backend-portable, so the fused/donation/seqlm windows can be
        # seeded from any box while the model1 headline waits for a
        # real accelerator run.
        f_rounds = args.rounds or (3 if args.smoke else 8)
        f_repeats = 2 if args.smoke else args.repeats
        tsize, esize = (4_096, 512) if args.smoke else (16_384, 2_048)
        result = _measure_fused_modes(
            train_size=tsize, test_size=esize, rounds=f_rounds,
            repeats=f_repeats, telemetry=tele, prefetch=args.prefetch,
            max_spread=0.0 if args.smoke else args.max_spread,
            hbm_rounds=None if args.smoke else 8)
        print(json.dumps(result))
        seqlm = None
        if not args.skip_seqlm:
            seqlm = _measure_seqlm(
                steps=args.seqlm_steps or (4 if args.smoke else 12),
                seq_len=args.seqlm_seq_len or (256 if args.smoke else 1_024),
                batch=2 if args.smoke else 4,
                repeats=1 if args.smoke else min(args.repeats, 3),
                telemetry=tele)
            print(json.dumps(seqlm))
        if args.history_out and not args.smoke:
            try:
                from dopt.obs.regress import append_entry

                for line in filter(None, (result, seqlm)):
                    entry = append_entry(args.history_out, line,
                                         run_id=args.run_id)
                    print(f"# appended run {entry['run_id']} "
                          f"({line['metric']}) to {args.history_out}",
                          file=sys.stderr)
            except OSError as e:
                print(f"# bench history append failed: {e}",
                      file=sys.stderr)
        _finish_telemetry(result)
        return

    if args.comm_modes:
        # Standalone r08 mode: the comm-substrate codec ablation only,
        # its own ledger key (the r06/r07 pattern).  The HLO byte basis
        # rides a CPU-pinned subprocess so the probe mesh can be
        # multi-device whatever backend this process holds.
        c_rounds = args.rounds or (3 if args.smoke else 8)
        c_repeats = 2 if args.smoke else args.repeats
        tsize, esize = (2_048, 512) if args.smoke else (8_192, 1_024)
        result = _measure_comm_modes(
            train_size=tsize, test_size=esize, rounds=c_rounds,
            repeats=c_repeats, telemetry=tele,
            conv_rounds=6 if args.smoke else 24,
            max_spread=0.0 if args.smoke else args.max_spread)
        print(json.dumps(result))
        if args.history_out and not args.smoke:
            try:
                from dopt.obs.regress import append_entry

                entry = append_entry(args.history_out, result,
                                     run_id=args.run_id)
                print(f"# appended run {entry['run_id']} "
                      f"(sha {entry['git_sha'] or 'unknown'}) to "
                      f"{args.history_out}", file=sys.stderr)
            except OSError as e:
                print(f"# bench history append failed: {e}",
                      file=sys.stderr)
        _finish_telemetry(result)
        return

    if args.quick:
        # CI-artifact mode: tiny data, two measured rounds per path —
        # enough to exercise both execution paths end to end and emit
        # the tracked JSON shape; the VALUE is only meaningful from a
        # real accelerator run (the full bench measures it properly).
        # Diagnostics ride the chaos legs so the metrics artifact
        # carries the convergence gauges + resource/compile events the
        # CI gate asserts on.
        chaos = _measure_chaos(1_536, 512, rounds=args.rounds or 2,
                               repeats=2, telemetry=tele,
                               prefetch=args.prefetch,
                               diagnostics="on")
        quick_line = {"metric": "gossip_rounds_per_sec_chaos",
                      "value": chaos["gossip_rounds_per_sec_chaos"],
                      "unit": "rounds/sec", "quick": True,
                      # The CI artifact contract: host_gap_pct present
                      # and finite even without a device-time basis
                      # (here: the host-timer estimate of the chaos
                      # blocked leg).
                      "host_gap_pct": chaos["chaos_host_gap_pct"],
                      "host_batch_plan_fraction":
                          chaos["chaos_host_batch_plan_fraction"],
                      "prefetch": args.prefetch,
                      "diagnostics": "on", **chaos}
        from dopt.utils.profiling import device_memory_stats

        mem = device_memory_stats()
        if mem is not None:
            # Finite peak HBM in the quick artifact (host RSS on the
            # CPU CI runner) — the other half of the CI gate.
            quick_line["hbm_peak_gb"] = round(mem["peak_bytes"] / 2**30, 3)
            quick_line["hbm_source"] = mem["source"]
        if args.fused == "on":
            # Fused-epilogue A/B on tiny data: both execution paths end
            # to end, so the quick artifact always carries finite
            # fused_rounds_per_sec + fused_speedup fields (the CI
            # present-and-finite assertion); the VALUES are only
            # meaningful from the full bench.
            quick_line.update(_measure_fused(
                train_size=1_536, test_size=512,
                rounds=args.rounds or 2, block=args.rounds or 2,
                repeats=2, prefetch=args.prefetch, telemetry=tele))
        print(json.dumps(quick_line))
        if not args.skip_clients:
            # Client-scale quick line: the 1k-client baseline3 cohort
            # loop end to end (sampling → 4-wave scan → hierarchical
            # reduce → registry feedback) on tiny data, one local
            # epoch — the CI artifact the full bench measures properly.
            popm = _measure_population(clients=1_000, cohort=64,
                                       train_size=1_536, test_size=512,
                                       rounds=args.rounds or 2,
                                       repeats=2, local_ep=1, model="mlp",
                                       telemetry=tele,
                                       prefetch=args.prefetch)
            print(json.dumps({**popm, "quick": True}))
            quick_line.update({f"clients_{k}": v for k, v in popm.items()
                               if isinstance(v, (int, float))})
        _finish_telemetry(quick_line)
        return

    train_size = 6_000 if args.smoke else 60_000
    test_size = 1_000 if args.smoke else 10_000
    # 20 measured rounds: one fused dispatch, ~12s — averages out the
    # ~10% run-to-run variance a 10-round window shows on this chip.
    rounds = args.rounds if args.rounds is not None else (3 if args.smoke else 20)
    if rounds <= 0:
        ap.error("--rounds must be positive")
    block = args.block if args.block is not None else rounds

    faithful_model = not args.idiomatic
    repeats = 2 if args.smoke else args.repeats
    device_blocks = 0 if args.smoke else args.device_blocks
    max_spread = 0.0 if args.smoke else args.max_spread
    fast = _measure(
        _config(fast=True, train_size=train_size, test_size=test_size,
                faithful_model=faithful_model,
                update_sharding=args.update_sharding,
                prefetch=args.prefetch),
        rounds, block, repeats, device_blocks=device_blocks,
        max_spread=max_spread, telemetry=tele)
    kind, peak = _device_peak_flops()
    fast_sps = fast["samples_per_sec"]
    result = {
        "metric": "gossip_rounds_per_sec_dsgd_mnist_6workers_model1_bf16"
                  + ("" if faithful_model else "_idiomatic"),
        "value": round(fast["rounds_per_sec"], 4),
        "unit": "rounds/sec",
        "vs_baseline": round(fast["rounds_per_sec"]
                             / REFERENCE_ROUNDS_PER_SEC, 2),
        "update_sharding": args.update_sharding,
        "prefetch": args.prefetch,
        # Host-gap headline (ROADMAP lever 2): device vs wall
        # rounds/sec when the device basis ran, else the host-timer
        # estimate — the number the prefetch overlap must close to <5%.
        "host_gap_pct": round(fast["host_gap_pct"], 2),
        "host_batch_plan_fraction": round(
            fast["host_batch_plan_fraction"], 4),
        "spread_pct": round(fast["spread_pct"], 2),
        "spread_pct_raw": round(fast["spread_pct_raw"], 2),
        "wall_retries": fast["wall_retries"],
        "measured_blocks": fast["measured_blocks_total"],
        "rounds_per_block": rounds,
        "fast_avg_test_acc": round(fast["avg_test_acc"], 4),
        "fast_total_trained_rounds": fast["total_trained_rounds"],
        "device_kind": kind,
        "samples_per_sec": round(fast_sps, 1),
        "model_tflops_per_sec": round(
            fast_sps * MODEL1_TRAIN_FLOPS_PER_SAMPLE / 1e12, 2),
        "bytes_on_wire": fast["bytes_on_wire"],
    }
    if "device_ms_per_round" in fast:
        # Device basis: what the chip actually spent, from the
        # profiler's device self-time over --device-blocks traced blocks.
        result["device_ms_per_round"] = round(fast["device_ms_per_round"], 2)
        result["device_rounds_per_sec"] = round(
            fast["device_rounds_per_sec"], 4)
        result["device_spread_pct"] = round(fast["device_spread_pct"], 2)
        result["device_blocks"] = device_blocks
    if "phase_fractions" in fast:
        # Conv / mixing-comm / update split of device time — the
        # measured basis for "conv fraction >= X%" claims (named-scope
        # attribution, dopt.utils.profiling.classify_phase).
        pf = fast["phase_fractions"]
        result["conv_fraction"] = pf["conv"]
        result["comm_fraction"] = pf["comm"]
        result["update_fraction"] = pf["update"]
        result["other_fraction"] = pf["other"]
    if peak:
        result["mfu_vs_bf16_peak"] = round(
            fast_sps * MODEL1_TRAIN_FLOPS_PER_SAMPLE / peak, 4)
    from dopt.utils.profiling import device_memory_stats

    mem = device_memory_stats()
    if mem is not None:
        # Peak HBM of the fast leg's process (backend allocator stats
        # on TPU/GPU, host RSS on CPU — `hbm_source` says which).
        result["hbm_peak_gb"] = round(mem["peak_bytes"] / 2**30, 3)
        result["hbm_source"] = mem["source"]
    if not args.skip_diagnostics:
        # Diagnostics-overhead leg: the IDENTICAL fast workload with
        # GossipConfig.diagnostics="on" (the on-device norm/spread/
        # consensus reductions + the packed-vector growth), so the
        # headline carries the measured cost of per-round
        # introspection.  The acceptance bar is < 5% rounds/sec.
        diag = _measure(
            _config(fast=True, train_size=train_size,
                    test_size=test_size, faithful_model=faithful_model,
                    update_sharding=args.update_sharding,
                    prefetch=args.prefetch, diagnostics="on"),
            rounds, block, repeats, max_spread=max_spread,
            telemetry=tele)
        result["diagnostics_rounds_per_sec"] = round(
            diag["rounds_per_sec"], 4)
        result["diagnostics_overhead_pct"] = round(
            100.0 * (1.0 - diag["rounds_per_sec"]
                     / fast["rounds_per_sec"]), 2)
        print(f"# diagnostics on: {diag['rounds_per_sec']:.4f} r/s vs "
              f"off {fast['rounds_per_sec']:.4f} r/s "
              f"({result['diagnostics_overhead_pct']:+.2f}% overhead)",
              file=sys.stderr)
    if args.fused == "on":
        # Fused-epilogue headline (ROADMAP raw-speed lever 3 landing):
        # the identical workload with the round epilogue as ONE
        # fused_mix_update pass vs the two-op reference — the ratio is
        # the ledger-tracked fused_speedup.
        fusedm = _measure_fused(
            train_size=train_size, test_size=test_size, rounds=rounds,
            block=block, repeats=repeats, faithful_model=faithful_model,
            prefetch=args.prefetch, max_spread=max_spread, telemetry=tele)
        result.update(fusedm)
        print(f"# fused epilogue: on {fusedm['fused_rounds_per_sec']:.4f} "
              f"r/s vs off {fusedm['fused_off_rounds_per_sec']:.4f} r/s "
              f"({fusedm['fused_speedup']:.2f}x; "
              f"acc={fusedm['fused_avg_test_acc']:.4f})", file=sys.stderr)
    if not args.skip_chaos:
        # Second headline: the degraded-network cocktail at blocked
        # (fused-scan) speed, with the pre-change per-round path timed
        # alongside so the dispatch-overhead win stays measured.
        chaos = _measure_chaos(train_size, test_size, rounds, repeats,
                               telemetry=tele, prefetch=args.prefetch)
        result.update(chaos)
        print(f"# chaos cocktail: blocked "
              f"{chaos['gossip_rounds_per_sec_chaos']:.4f} r/s vs "
              f"per-round {chaos['chaos_per_round_rounds_per_sec']:.4f} "
              f"r/s ({chaos['chaos_speedup_vs_per_round']:.2f}x; "
              f"acc={chaos['chaos_avg_test_acc']:.4f})", file=sys.stderr)
    if not args.skip_clients:
        # Client-scale headlines (dopt.population): clients/sec served
        # at population 1k (cohort 64 → 4 waves) and 10k (cohort 256 →
        # 16 waves) on baseline3 — each its own JSON line, with the
        # summary numbers folded into the main line.
        for n_clients, cohort in ((1_000, 64), (10_000, 256)):
            popm = _measure_population(
                clients=n_clients, cohort=cohort, train_size=train_size,
                test_size=test_size,
                rounds=max(rounds // 4, 2) if not args.smoke else 2,
                repeats=repeats, telemetry=tele,
                prefetch=args.prefetch)
            result[f"clients_per_sec_{n_clients // 1000}k"] = popm["value"]
            print(f"# clients/sec @ population={n_clients} "
                  f"(cohort {cohort}, {popm['waves']} waves): "
                  f"{popm['value']:.1f} "
                  f"({popm['rounds_per_sec']:.3f} rounds/s, "
                  f"acc={popm['final_test_acc']:.4f})", file=sys.stderr)
            print(json.dumps(popm))
    if not args.skip_topology:
        # r06 legs: the mixing-pattern ablation at n=32 rides the full
        # bench too (own JSON line; the ratios fold into the headline
        # so the regress ledger watches the one-peer/async wire win).
        topo = _measure_topology_modes(
            train_size=4_096 if args.smoke else 16_384,
            test_size=512 if args.smoke else 2_048,
            rounds=rounds, repeats=repeats, telemetry=tele,
            prefetch=args.prefetch, max_spread=max_spread)
        print(json.dumps(topo))
        for k in ("dense_rounds_per_sec", "one_peer_rounds_per_sec",
                  "async_rounds_per_sec", "one_peer_speedup_vs_dense",
                  "async_speedup_vs_dense", "async_speedup_vs_one_peer",
                  "one_peer_avg_test_acc", "async_avg_test_acc"):
            result[k] = topo[k]
    if not args.skip_faithful:
        faith = _measure(
            _config(fast=False, train_size=train_size, test_size=test_size,
                    faithful_model=faithful_model),
            rounds, block, repeats)
        result["faithful_f32_rounds_per_sec"] = round(
            faith["rounds_per_sec"], 4)
        result["faithful_f32_vs_baseline"] = round(
            faith["rounds_per_sec"] / REFERENCE_ROUNDS_PER_SEC, 2)
        result["faithful_avg_test_acc"] = round(faith["avg_test_acc"], 4)
        result["faithful_total_trained_rounds"] = faith[
            "total_trained_rounds"]
        result["faithful_samples_per_sec"] = round(
            faith["samples_per_sec"], 1)
        result["faithful_spread_pct"] = round(faith["spread_pct"], 2)
        print(f"# faithful f32: {repeats}x{rounds} rounds in "
              f"{faith['measured_seconds']:.2f}s (median, spread "
              f"{faith['spread_pct']:.1f}%; acc={faith['avg_test_acc']:.4f}, "
              f"{faith['samples_per_sec']:,.0f} samples/s)", file=sys.stderr)
    seqlm = None
    if not args.skip_seqlm:
        # seqlm headline leg (promoted from scripts/bench_seqlm.py):
        # its own JSON line and its own ledger entry, judged under the
        # (seqlm_tokens_per_sec, device_kind) key — a first-seen key
        # reports NO_BASELINE until its window fills.
        seqlm = _measure_seqlm(
            steps=args.seqlm_steps or (4 if args.smoke else 30),
            seq_len=args.seqlm_seq_len or (256 if args.smoke else 2_048),
            batch=2 if args.smoke else 8,
            repeats=1 if args.smoke else min(repeats, 3),
            telemetry=tele)
        print(f"# seqlm: {seqlm['value']:,.1f} tokens/s "
              f"(seq_len={seqlm['seq_len']}, batch={seqlm['batch']}, "
              f"{seqlm['mesh_devices']} device(s), "
              f"loss={seqlm['final_loss']:.4f})", file=sys.stderr)
        print(json.dumps(seqlm))
    print(f"# fast bf16: {repeats}x{rounds} rounds in "
          f"{fast['measured_seconds']:.2f}s (median, spread "
          f"{fast['spread_pct']:.1f}%; acc={fast['avg_test_acc']:.4f}, "
          f"{fast_sps:,.0f} samples/s)", file=sys.stderr)
    print(json.dumps(result))
    if args.history_out and not args.smoke:
        # The bench trajectory as a ledger: one entry per real run, so
        # the NEXT run can be judged against the trailing trimmed
        # median (dopt.obs.regress).  Never fatal — a read-only
        # checkout still benches.
        try:
            from dopt.obs.regress import append_entry

            entry = append_entry(args.history_out, result,
                                 run_id=args.run_id)
            print(f"# appended run {entry['run_id']} "
                  f"(sha {entry['git_sha'] or 'unknown'}) to "
                  f"{args.history_out}", file=sys.stderr)
            if seqlm is not None:
                s_entry = append_entry(args.history_out, seqlm,
                                       run_id=args.run_id)
                print(f"# appended run {s_entry['run_id']} "
                      f"({s_entry['metric']}) to {args.history_out}",
                      file=sys.stderr)
        except OSError as e:
            print(f"# bench history append failed: {e}", file=sys.stderr)
    _finish_telemetry(result)


if __name__ == "__main__":
    main()
