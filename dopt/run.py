"""CLI experiment runner: ``python -m dopt.run --preset reference-fedavg``.

The typed replacement for the reference's notebook driver cells: pick a
preset (or override fields), run, print per-round metrics, export the
history CSV in the reference's results layout, optionally checkpoint.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import re
import sys


def apply_override(cfg, spec: str):
    """``--set path.to.field=value``: frozen-dataclass field override by
    dotted path.  The value is coerced from the FIELD ANNOTATION (not
    the current value, which may be None), with strict bool parsing and
    clean SystemExit errors for every bad input."""
    path, eq, raw = spec.partition("=")
    if not eq:
        raise SystemExit(f"--set expects PATH=VALUE, got {spec!r}")
    parts = path.split(".")
    objs = [cfg]
    for p in parts[:-1]:
        names = {f.name for f in dataclasses.fields(objs[-1])}
        if p not in names:
            raise SystemExit(f"--set: {path!r} not found on this preset")
        nxt = getattr(objs[-1], p)
        if not dataclasses.is_dataclass(nxt):
            raise SystemExit(
                f"--set: {'.'.join(parts[:parts.index(p) + 1])!r} is not "
                f"configured on this preset (value: {nxt!r})")
        objs.append(nxt)
    leaf = parts[-1]
    fields = {f.name: f for f in dataclasses.fields(objs[-1])}
    if leaf not in fields:
        raise SystemExit(f"--set: {path!r} not found on this preset")
    ann = str(fields[leaf].type)
    m = re.match(r"[A-Za-z_]+", ann.strip())
    primary = m.group(0) if m else ann
    if primary not in ("bool", "int", "float", "str"):
        # Checked FIRST so optional non-scalar subtrees (e.g.
        # `gossip: GossipConfig | None`) can't be nulled via the
        # none/null branch and crash later.
        raise SystemExit(
            f"--set: field {path!r} of type {ann!r} is not settable "
            "from the CLI")
    if raw.lower() in ("none", "null") and "None" in ann:
        val = None
    elif primary == "bool":
        low = raw.lower()
        if low in ("1", "true", "yes"):
            val = True
        elif low in ("0", "false", "no"):
            val = False
        else:
            raise SystemExit(
                f"--set: {path!r} is a bool; use true/false, got {raw!r}")
    elif primary == "int":
        try:
            val = int(raw)
        except ValueError:
            raise SystemExit(f"--set: {path!r} expects an int, got {raw!r}")
    elif primary == "float":
        try:
            val = float(raw)
        except ValueError:
            raise SystemExit(f"--set: {path!r} expects a float, got {raw!r}")
    else:  # primary == "str"
        val = raw
    new = dataclasses.replace(objs[-1], **{leaf: val})
    for obj, name in zip(reversed(objs[:-1]), reversed(parts[:-1])):
        new = dataclasses.replace(obj, **{name: new})
    return new


def build_trainer(cfg):
    if cfg.backend not in ("jax", "torch"):
        raise ValueError(
            f"unknown backend {cfg.backend!r}; 'jax' (TPU/mesh engines) or "
            "'torch' (the sequential reference oracle)")
    if cfg.backend == "torch":
        from dopt.engine.torch_backend import build_torch_trainer

        return build_torch_trainer(cfg)
    from dopt.engine import FederatedTrainer, GossipTrainer, SeqLMTrainer

    if cfg.seqlm is not None:
        return SeqLMTrainer(cfg)
    if cfg.federated is not None:
        return FederatedTrainer(cfg)
    return GossipTrainer(cfg)


def device_details(trainer) -> str:
    """What the run executes on, printed beside ``exp_details`` so every
    log names its device (a CPU run must never read as a chip run)."""
    import jax

    devs = jax.devices()
    line = (f"device: platform={devs[0].platform} "
            f"device_kind={devs[0].device_kind!r} n_devices={len(devs)} "
            f"mesh={dict(trainer.mesh.shape)}")
    # A decoder says which body its attention runs at this row length
    # (dopt.models.decoder.causal_attention) and which its held experts
    # (dopt.ops.grouped_experts.path): the fused kernels or jax.numpy.
    model = getattr(trainer, "model", None)
    path = getattr(model, "attention_path", None)
    if path is not None:
        line += (f" attention={path(trainer.cfg.model.input_shape[0])}"
                 f" experts={model.expert_path()}")
    return line


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--preset", required=True,
                    help="preset name (see dopt.presets.PRESETS) or 'list'")
    ap.add_argument("--rounds", type=int, default=None,
                    help="override round count")
    ap.add_argument("--num-users", type=int, default=None)
    ap.add_argument("--synthetic-scale", type=float, default=None,
                    help="scale synthetic dataset sizes (e.g. 0.1 for smoke)")
    ap.add_argument("--csv", default=None, help="write history CSV here")
    ap.add_argument("--checkpoint", default=None,
                    help="save a checkpoint here after the run")
    ap.add_argument("--checkpoint-every", type=int, default=0, metavar="K",
                    help="auto-checkpoint to --checkpoint every K rounds "
                         "during the run (crash-exact: a run killed at any "
                         "point and restarted with --resume is bit-identical "
                         "to a continuous run); federated/gossip jax "
                         "engines only")
    ap.add_argument("--resume", default=None,
                    help="restore this checkpoint before running (pair with "
                         "--checkpoint-every for kill-and-resume workflows)")
    ap.add_argument("--faults", default=None, metavar="SPEC",
                    help="inject deterministic faults "
                         "(dopt.faults.FaultPlan): comma-separated "
                         "FaultConfig fields, e.g. "
                         "'crash=0.1,straggle=0.2,straggle_frac=0.5,"
                         "partition=0.05' or the lossy-link/elastic knobs "
                         "'msg_drop=0.1,msg_delay=0.2,msg_delay_max=2,"
                         "churn=0.02,churn_span=4'; every injected fault is "
                         "recorded in the run's fault ledger.  Pair "
                         "asymmetric msg_drop with --set "
                         "gossip.correction=push_sum (bias-free consensus) "
                         "and msg_delay/straggler drops with --set "
                         "federated.staleness_max=K (late updates admitted "
                         "with decay instead of lost)")
    ap.add_argument("--corrupt", default=None, metavar="SPEC",
                    help="inject Byzantine corruption (workers that LIE): "
                         "'p=0.25,mode=signflip,scale=50,max=2' or a bare "
                         "probability; merges onto --faults so crash and "
                         "corruption compose.  modes: nan|inf|scale|"
                         "signflip|stale; with p=1 'max=f' pins workers "
                         "0..f-1 as persistent adversaries")
    ap.add_argument("--aggregator", default=None,
                    choices=["mean", "trimmed_mean", "median", "krum",
                             "multi_krum"],
                    help="Byzantine-robust aggregation (dopt.robust): how "
                         "the federated server combines surviving updates "
                         "(default mean).  Tune the knobs with --set "
                         "robust.trim_frac=... etc.; the gossip engine's "
                         "defense is clipped gossip: pass "
                         "'--aggregator mean --set robust.clip_radius=R' "
                         "(the flag installs the robust section)")
    ap.add_argument("--clients", type=int, default=None, metavar="N",
                    help="client population registry (dopt.population): "
                         "sample each round's cohort from N host-side "
                         "client records instead of equating workers with "
                         "device lanes; the cohort trains in "
                         "ceil(cohort/lanes) waves with hierarchical "
                         "(bucketed reduce-scatter) aggregation.  Pair "
                         "with --cohort/--cohort-seed; tune the lane "
                         "width with --set population.lanes=W")
    ap.add_argument("--cohort", type=int, default=None, metavar="M",
                    help="clients sampled per round (default 64; requires "
                         "--clients or a population preset)")
    ap.add_argument("--cohort-seed", type=int, default=None, metavar="S",
                    help="cohort-sampler seed (default: the experiment "
                         "seed); draws are stateless per (seed, round)")
    ap.add_argument("--faults-json", default=None, metavar="PATH",
                    help="write the run's fault ledger here as JSON")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="stream structured telemetry (dopt.obs) to this "
                         "JSONL file: one versioned event per line — "
                         "per-round 'round' events (the history row), "
                         "typed 'fault' events (the ledger), and 'gauge' "
                         "events (quarantine/staleness/population state, "
                         "end-of-run consensus distance).  With --resume "
                         "the stream APPENDS and continues from its round "
                         "watermark (no duplicated or missing rounds); "
                         "validate with 'python -m dopt.obs.check PATH'")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome-trace/Perfetto JSON of the host "
                         "spans (batch planning, fused block dispatches, "
                         "checkpoint writes) here — the host-side "
                         "companion to the XLA trace from --trace")
    ap.add_argument("--diagnostics", choices=("off", "on"), default=None,
                    help="per-round on-device convergence diagnostics "
                         "(GossipConfig/FederatedConfig.diagnostics): "
                         "'on' emits update/grad/param norms, lane-loss "
                         "spread and the per-round consensus distance / "
                         "lane dispersion as deterministic gauges, plus "
                         "HBM 'resource' samples and 'compile' retrace "
                         "events, into --metrics-out; default keeps the "
                         "preset's setting ('off' = the exact pre-change "
                         "programs)")
    ap.add_argument("--timers", action="store_true",
                    help="print phase-timer report")
    ap.add_argument("--trace", default=None, metavar="DIR",
                    help="capture a jax/XLA profiler trace of the run "
                         "into DIR (view with tensorboard or xprof)")
    ap.add_argument("--set", action="append", default=[], metavar="PATH=VAL",
                    dest="overrides",
                    help="override any config field by dotted path, e.g. "
                         "--set gossip.topology=hierarchical "
                         "--set optim.lr=0.05 --set seed=7; value is coerced "
                         "to the field's annotated type; for optional "
                         "fields (e.g. gossip.comm_dtype) the literal "
                         "strings 'none'/'null' set the field to None — "
                         "they cannot be passed as string values there")
    args = ap.parse_args(argv)

    from dopt.presets import PRESETS, get_preset

    if args.preset == "list":
        for name in sorted(PRESETS):
            print(name)
        return 0

    cfg = get_preset(args.preset)
    if args.aggregator:
        # Installed BEFORE --set so `--aggregator krum --set
        # robust.krum_f=2` works on presets without a robust section.
        from dopt.config import RobustConfig

        base = cfg.robust or RobustConfig()
        cfg = cfg.replace(
            robust=dataclasses.replace(base, aggregator=args.aggregator))
    for spec in args.overrides:
        cfg = apply_override(cfg, spec)
    if args.faults:
        from dopt.faults import parse_fault_spec

        try:
            cfg = cfg.replace(faults=parse_fault_spec(args.faults))
        except ValueError as e:
            raise SystemExit(str(e))
    if args.corrupt:
        from dopt.faults import parse_corrupt_spec

        try:
            cfg = cfg.replace(
                faults=parse_corrupt_spec(args.corrupt, base=cfg.faults))
        except ValueError as e:
            raise SystemExit(str(e))
    if cfg.faults is not None and (cfg.seqlm is not None
                                   or cfg.backend == "torch"):
        # The torch oracle and seqlm engines never read cfg.faults —
        # reject loudly instead of running "fault-free" with an empty
        # ledger the user believes is a faulted run.
        raise SystemExit("fault injection is supported by the "
                         "federated/gossip jax engines only")
    if (args.clients is not None or args.cohort is not None
            or args.cohort_seed is not None):
        from dopt.config import PopulationConfig
        from dopt.population import validate_population_config

        base_pop = cfg.population
        if args.clients is None and base_pop is None:
            raise SystemExit("--cohort/--cohort-seed need --clients N (or "
                             "a preset with a population section)")
        pop_kw = {}
        if args.clients is not None:
            pop_kw["clients"] = args.clients
        if args.cohort is not None:
            pop_kw["cohort"] = args.cohort
        if args.cohort_seed is not None:
            pop_kw["seed"] = args.cohort_seed
        pop = dataclasses.replace(base_pop or PopulationConfig(), **pop_kw)
        try:
            validate_population_config(pop)
        except ValueError as e:
            raise SystemExit(str(e))
        cfg = cfg.replace(population=pop)
    if cfg.population is not None and (cfg.seqlm is not None
                                       or cfg.backend == "torch"):
        # Same contract as faults: the torch oracle and seqlm engines
        # never read cfg.population — reject instead of silently running
        # the classic worker==lane experiment.
        raise SystemExit("the client population registry is supported by "
                         "the federated/gossip jax engines only")
    if args.diagnostics is not None:
        if cfg.gossip is not None:
            cfg = cfg.replace(gossip=dataclasses.replace(
                cfg.gossip, diagnostics=args.diagnostics))
        elif cfg.federated is not None:
            cfg = cfg.replace(federated=dataclasses.replace(
                cfg.federated, diagnostics=args.diagnostics))
        else:
            # Same contract as --faults/--metrics-out: the torch oracle
            # and seqlm engines carry no diagnostics layer.
            raise SystemExit("--diagnostics is supported by the "
                             "federated/gossip jax engines only")
    if args.num_users is not None:
        cfg = cfg.replace(data=dataclasses.replace(cfg.data,
                                                   num_users=args.num_users))
    if args.synthetic_scale is not None:
        cfg = cfg.replace(data=dataclasses.replace(
            cfg.data,
            synthetic_train_size=max(int(cfg.data.synthetic_train_size
                                         * args.synthetic_scale),
                                     cfg.data.num_users * 8),
            synthetic_test_size=max(int(cfg.data.synthetic_test_size
                                        * args.synthetic_scale), 64),
        ))

    from dopt.config import exp_details
    from dopt.utils.compile_cache import enable_compile_cache

    print(exp_details(cfg), file=sys.stderr)
    enable_compile_cache()
    trainer = build_trainer(cfg)
    if cfg.backend == "jax":
        print(device_details(trainer), file=sys.stderr)
    if args.resume:
        trainer.restore(args.resume)
        print(f"resumed at round {trainer.round}", file=sys.stderr)

    tele = None
    if args.metrics_out or args.trace_out:
        if cfg.seqlm is not None or cfg.backend == "torch":
            # Same contract as --faults: only the federated/gossip jax
            # engines carry the emission sites — reject instead of
            # writing an empty stream the user believes is telemetry.
            raise SystemExit("--metrics-out/--trace-out are supported by "
                             "the federated/gossip jax engines only")
        from dopt.obs import Telemetry, attach

        tele = (Telemetry.to_jsonl(args.metrics_out,
                                   resume=bool(args.resume))
                if args.metrics_out else Telemetry())
        attach(trainer, tele,
               checkpoint_every=args.checkpoint_every or None)

    rounds = args.rounds
    if rounds is None:
        if cfg.seqlm is not None:
            rounds = cfg.seqlm.steps
        elif cfg.federated is not None:
            rounds = cfg.federated.rounds
        else:
            rounds = cfg.gossip.rounds
    run_kw = {}
    if args.checkpoint_every:
        if not args.checkpoint:
            raise SystemExit("--checkpoint-every requires --checkpoint PATH")
        if cfg.seqlm is not None or cfg.backend == "torch":
            raise SystemExit("--checkpoint-every is supported by the "
                             "federated/gossip jax engines only")
        run_kw = {"checkpoint_every": args.checkpoint_every,
                  "checkpoint_path": args.checkpoint}
    if args.trace:
        from dopt.utils.profiling import trace

        with trace(args.trace):
            trainer.run(rounds=rounds, **run_kw)
        print(f"wrote XLA trace to {args.trace}", file=sys.stderr)
    else:
        trainer.run(rounds=rounds, **run_kw)
    rows = trainer.history.rows[-min(rounds, len(trainer.history)):]
    for row in rows:
        print(json.dumps(row))
    print(f"total_time_s={trainer.total_time:.2f}", file=sys.stderr)

    if args.timers:
        print(trainer.timers.report(), file=sys.stderr)
        # A sequence model's routing counts (dopt.models.decoder), which
        # each round's history row carries: their mean over this run.
        for name in getattr(trainer, "counters", ()):
            mean = sum(r[name] for r in rows) / len(rows)
            print(f"counter {name}: mean {mean:.6g} over {len(rows)} rounds",
                  file=sys.stderr)
    if args.csv:
        trainer.history.to_csv(args.csv)
        print(f"wrote {args.csv}", file=sys.stderr)
    if getattr(trainer.history, "faults", None):
        print(f"fault ledger: {len(trainer.history.faults)} entries",
              file=sys.stderr)
    if args.faults_json:
        trainer.history.faults_to_json(args.faults_json)
        print(f"wrote fault ledger to {args.faults_json}", file=sys.stderr)
    if args.checkpoint:
        trainer.save(args.checkpoint)
        print(f"checkpointed to {args.checkpoint}", file=sys.stderr)
    if tele is not None:
        # Closed AFTER the final --checkpoint save: the engines emit a
        # `checkpoint` telemetry event when a save lands, and a closed
        # sink would turn the last one into an I/O error.
        tele.close()
        if args.metrics_out:
            print(f"wrote telemetry stream to {args.metrics_out}",
                  file=sys.stderr)
        if args.trace_out:
            tele.write_trace(args.trace_out)
            print(f"wrote host span trace to {args.trace_out}",
                  file=sys.stderr)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
