"""Does dopt still start on the chip?  ``python chip_smoke.py [LEG ...]``

Drives the system's main path once on the accelerator, in ONE process,
through the entry point a user calls (``dopt.run.main`` — what
``python -m dopt.run`` runs) on presets the repo ships, unscaled:
random weights and synthetic data from the seed, a few rounds each.

Legs (each prints one JSON line; any failure makes the exit code 1):

* ``gossip``     ``--preset baseline5 --rounds 3`` (32 x ResNet-18)
* ``federated``  ``--preset baseline3 --rounds 3`` (FedAvg, Model1)
* ``scatter`` / ``prefetch`` / ``fused`` / ``codec`` — the default-off
  paths on the headline shape (6 x Model1 ring, ``local_bs=128``); the
  fused leg also proves its Pallas epilogue is Mosaic-COMPILED (a
  ``tpu_custom_call`` in the compiled round) and agrees with
  ``mix_sgd_reference``
* ``trace``      one round under ``--trace DIR`` reduced by
  ``xplane_op_stats``: device self time and the phase split
* ``consensus``  a doubly-stochastic mix preserves every leaf's
  worker-mean to f32 tolerance (TPU matmuls default to bf16 passes)
* ``multichip``  (>= 4 devices) baseline5 on 4 chips, an 8-worker ring
  over ``ppermute`` and its scatter twin against the 1-device run

It sets no platform.  Without a TPU it exits 1 in seconds, naming the
backend it found, and prints no result.  The last stdout line is
``{"ok": true, "device": {"platform", "kind", "count"}}``.  These are
set-up facts (did it run, on what, how long to compile) — not metrics.
"""

from __future__ import annotations

import contextlib
import dataclasses
import io
import json
import math
import re
import shutil
import sys
import tempfile
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

import dopt.run
from dopt.config import CommConfig
from dopt.presets import get_preset
from dopt.utils.compile_cache import enable_compile_cache

HEADLINE = "reference-dsgd-circle"   # 6 x Model1, ring, local_bs=128
OUT_DIR = Path(__file__).resolve().parent / "chiprun_out"


def device_info() -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind, "n_devices": len(devs)}


class CompileMeter:
    """Seconds spent in backend compilation (or loading from the
    persistent cache) and persistent-cache hits, from ``jax.monitoring``
    — so each leg can report compile apart from steady time."""

    def __init__(self) -> None:
        self.compile_s = 0.0
        self.cache_hits = 0

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def __enter__(self) -> "CompileMeter":
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc) -> None:
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def run_cli(argv: list[str]) -> tuple[list[dict], str]:
    """``dopt.run.main(argv)`` with its stdout captured: the history
    rows (one JSON line per round) and the mesh its ``device:`` line
    names (printed, not assumed: six workers take three chips of four)."""
    out, err = io.StringIO(), io.StringIO()
    try:
        with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
            rc = dopt.run.main(argv)
    finally:
        sys.stderr.write(err.getvalue())
    check(rc == 0, f"dopt.run.main({argv}) returned {rc}")
    mesh = re.search(r"^device: .* mesh=(\{.*\})$", err.getvalue(), re.M)
    check(mesh is not None, "dopt.run printed no device line")
    rows = [json.loads(ln) for ln in out.getvalue().splitlines()
            if ln.startswith("{")]
    return rows, mesh.group(1)


def check_rows(rows: list[dict], rounds: int) -> dict:
    """The repo's own evidence that rounds happened: one history row per
    round, in order, with a finite train loss and accuracies in [0, 1]."""
    check([r["round"] for r in rows] == list(range(rounds)),
          f"expected rounds 0..{rounds - 1}, got {rows}")
    losses = [r.get("avg_train_loss", r.get("train_loss")) for r in rows]
    check(all(v is not None and math.isfinite(v) for v in losses),
          f"non-finite train loss in {losses}")
    for r in rows:
        for k, v in r.items():
            if k.endswith("_acc"):
                check(0.0 <= v <= 1.0, f"{k}={v} outside [0, 1]")
    return {"rounds": len(rows), "last_train_loss": losses[-1]}


def leg_cli(argv: list[str]) -> dict:
    rounds = int(argv[argv.index("--rounds") + 1])
    rows, mesh = run_cli(argv)
    return {**check_rows(rows, rounds), "mesh": mesh}


def leg_baseline5_on(devices: int, *, rounds: int = 2) -> dict:
    """baseline5 folded onto ``devices`` chips (8 lanes each on four):
    the CLI must name that mesh, and every chip must have held its
    share of the 1.4 GB stacked state."""
    out = leg_cli(["--preset", "baseline5", "--rounds", str(rounds),
                   "--set", f"mesh_devices={devices}"])
    check(out["mesh"] == str({"workers": devices}), f"mesh {out['mesh']}")
    peaks = {d.id: d.memory_stats()["peak_bytes_in_use"]
             for d in jax.devices()[:devices]}
    check(all(v > (256 << 20) for v in peaks.values()),
          f"peak bytes per device {peaks}")
    out["peak_gib_per_device"] = {k: round(v / 2**30, 2)
                                  for k, v in peaks.items()}
    return out


def leg_codec(cfg, *, rounds: int = 2, comm: CommConfig | None = None) -> dict:
    """The q8 bucket codec, API-armed (``comm`` is None on every preset,
    so the CLI cannot reach it): scatter + qsgd with error feedback."""
    comm = comm or CommConfig(codec="qsgd")
    cfg = cfg.replace(
        comm=comm,
        gossip=dataclasses.replace(cfg.gossip, update_sharding="scatter"))
    tr = dopt.run.build_trainer(cfg)
    check("q8" in tr._codec_plan.kinds,
          f"codec plan has no q8 bucket: {tr._codec_plan.kinds}")
    tr.run(rounds=rounds)
    check(all(bool(jnp.isfinite(x).all()) for x in jax.tree.leaves(tr.params)),
          "non-finite params after codec rounds")
    out = check_rows(tr.history.rows, rounds)
    out["plan_kinds"] = sorted(set(tr._codec_plan.kinds))
    return out


def leg_fused(cfg, argv: list[str]) -> dict:
    """``fused_update=on``: the compiled round must contain the Mosaic
    kernel (never an interpreted one) on the chip, one epilogue call
    must agree with the jnp reference, and the CLI run must train."""
    from dopt.ops.fused_update import fused_mix_update, mix_sgd_reference
    from dopt.parallel.collectives import make_update_shard_spec

    cfg = cfg.replace(
        mesh_devices=1,
        gossip=dataclasses.replace(cfg.gossip, fused_update="on"))
    probe = dopt.run.build_trainer(cfg)   # lower_round wants a fresh one
    _, lowered = probe.lower_round()
    compiled_calls = lowered.compile().as_text().count("tpu_custom_call")
    on_tpu = jax.default_backend() == "tpu"
    check((compiled_calls > 0) == on_tpu,
          f"{compiled_calls} tpu_custom_call(s) in the compiled fused "
          f"round on backend {jax.default_backend()!r}")

    # One epilogue call on seeded state of the trainer's own shapes.
    n = probe.num_workers
    rng = np.random.default_rng(0)
    params = jax.tree.map(
        lambda x: jnp.asarray(rng.normal(size=x.shape), x.dtype),
        probe.params)
    buf = jax.tree.map(
        lambda x: jnp.asarray(0.1 * rng.normal(size=x.shape), x.dtype),
        probe.params)
    w = jnp.asarray(probe.mixing.for_round(0), jnp.float32)
    spec = make_update_shard_spec(params, fold=1)
    got = fused_mix_update(params, buf, w, spec, lr=1.0)
    with jax.default_matmul_precision("highest"):
        want = mix_sgd_reference(params, buf, w, lr=1.0)
    err = max(float(jnp.abs(a - b).max()) for a, b in
              zip(jax.tree.leaves(got), jax.tree.leaves(want)))
    check(err < 1e-5, f"fused epilogue vs mix_sgd_reference: max|d|={err}")

    out = leg_cli(argv)
    out.update(workers=n, tpu_custom_calls=compiled_calls,
               epilogue_max_abs_err=err)
    return out


def leg_trace(argv: list[str], *, need_device_time: bool) -> dict:
    """One round through the normal ``--trace DIR`` path, reduced by
    ``xplane_op_stats`` itself (``device_stats_of`` would swallow a
    broken reduction).  The reduced table lands in ``chiprun_out/``."""
    from dopt.utils.profiling import xplane_op_stats

    tdir = tempfile.mkdtemp(prefix="chip-smoke-trace-")
    try:
        out = leg_cli([*argv, "--trace", tdir])
        stats = xplane_op_stats(tdir)
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    OUT_DIR.mkdir(exist_ok=True)
    (OUT_DIR / "chip_smoke_trace.json").write_text(
        json.dumps({"device": device_info(), **stats}, indent=1))
    # xprof reports the device's idle time as an op type of its own
    # (here the window opens before the round compiles, so it is most
    # of it); what proves the reduction reads this chip's planes is
    # time in real ops, and that the engines' named scopes survive.
    idle_us = sum(c["self_time_us"] for c in stats["device_categories"]
                  if c["op_type"] == "IDLE")
    busy_us = stats["device_self_time_us"] - idle_us
    phases = stats["device_phases"]
    if need_device_time:
        check(math.isfinite(busy_us) and busy_us > 0,
              f"device self time {busy_us!r} (idle {idle_us!r}) from the "
              "trace")
        check(phases["update_us"] > 0,
              f"no time under the dopt_update scope: {phases}")
    out.update(device_busy_us=round(busy_us, 1),
               device_idle_us=round(idle_us, 1),
               phase_us={k: phases[f"{k}_us"] for k in
                         ("conv", "comm", "update")})
    return out


def leg_consensus(n: int = 6, width: int = 1 << 16) -> dict:
    """Worker-mean preservation under a doubly-stochastic mix, on every
    dense worker-axis contraction the engines use.  1/3 is not a bf16
    number: a contraction left at the TPU's default precision fails."""
    from dopt.ops.fused_update import fused_mix_update
    from dopt.parallel.collectives import (buckets_to_stacked,
                                           make_update_shard_spec,
                                           mix_dense, mix_dense_scatter,
                                           stacked_to_buckets)
    from dopt.parallel.mesh import make_mesh
    from dopt.topology import build_mixing_matrices

    mixing = build_mixing_matrices("circle", "metropolis", n, seed=0)
    check(mixing.is_doubly_stochastic(),
          "ring/metropolis matrix is not doubly stochastic")
    w = mixing.for_round(0)
    k1, k2 = jax.random.split(jax.random.key(1))
    tree = {"a": 1.0 + jax.random.normal(k1, (n, width)),
            "b": 3.0 * jax.random.normal(k2, (n, 33, 7))}
    mesh = make_mesh(1)
    spec = make_update_shard_spec(tree, fold=1)
    zeros = jax.tree.map(jnp.zeros_like, tree)
    mixes = {
        "mix_dense": lambda t: mix_dense(t, w, mesh),
        "mix_dense_scatter": lambda t: buckets_to_stacked(
            mix_dense_scatter(stacked_to_buckets(t, spec), w, mesh), spec),
        "fused_mix_update": lambda t: fused_mix_update(t, zeros, w, spec,
                                                       lr=1.0),
    }
    before = jax.tree.map(lambda x: x.mean(axis=0), tree)
    drift = {}
    for name, mix in mixes.items():
        after = jax.tree.map(lambda x: x.mean(axis=0), jax.jit(mix)(tree))
        drift[name] = max(float(jnp.abs(a - b).max()) for a, b in
                          zip(jax.tree.leaves(after),
                              jax.tree.leaves(before)))
    check(all(d < 5e-6 for d in drift.values()),
          f"worker-mean drift after one doubly-stochastic mix: {drift}")
    return {"workers": n, "mean_drift": drift}


def _spread_over(tr, devices: int) -> None:
    for leaf in jax.tree.leaves(tr.params):
        check(len(leaf.sharding.device_set) == devices,
              f"params leaf on {len(leaf.sharding.device_set)} device(s), "
              f"wanted {devices}")
    if jax.default_backend() != "cpu":   # the CPU client keeps no stats
        for d in sorted(tr.mesh.devices.flat, key=lambda d: d.id):
            used = d.memory_stats()["bytes_in_use"]
            check(used > 0, f"{d} reports bytes_in_use={used}")


def leg_ring_parity(cfg, *, devices: int = 4,
                    update_sharding: str = "off") -> dict:
    """An 8-worker ring on ``devices`` chips takes the ``ppermute``
    path, really spreads its state, and lands where the 1-device run of
    the same config lands after round 1 (round 0 mixes identical
    initial params, so only the second round tests the collective).

    Both runs trace at ``highest`` matmul precision, so that what is
    left between 2 lanes a chip and 8 lanes on one is f32 rounding and
    the bound can be tight enough to expose a wrong neighbour."""
    cfg = cfg.replace(gossip=dataclasses.replace(
        cfg.gossip, update_sharding=update_sharding))
    with jax.default_matmul_precision("highest"):
        many = dopt.run.build_trainer(cfg.replace(mesh_devices=devices))
        print(f"# ring mesh: {dict(many.mesh.shape)} over "
              f"{[d.id for d in many.mesh.devices.flat]}", file=sys.stderr)
        check(many.mesh.size == devices, f"mesh {dict(many.mesh.shape)}")
        check(many._shift_ids is not None,
              "ring did not take the shift path")
        many.run(rounds=2)
        _spread_over(many, devices)
        one = dopt.run.build_trainer(cfg.replace(mesh_devices=1))
        one.run(rounds=2)
    pairs = [(np.asarray(a), np.asarray(b)) for a, b in
             zip(jax.tree.leaves(many.params), jax.tree.leaves(one.params))]
    err = max(float(np.abs(a - b).max()) for a, b in pairs)
    spread = max(float((a.max(0) - a.min(0)).max()) for a, _ in pairs)
    check(err < 1e-5, f"{devices}-device vs 1-device params: max|d|={err} "
                      f"(workers sit {spread} apart)")
    out = check_rows(many.history.rows, 2)
    out.update(mesh=str(dict(many.mesh.shape)),
               shift_ids=list(many._shift_ids),
               vs_one_device_max_abs_err=err, worker_spread=spread)
    return out


def ring8_cfg():
    """The headline shape widened to 8 workers and cut to two local
    steps a round.  Parity to f32 tolerance is about the collective:
    with 16 steps a round the 4-chip and 1-chip runs part by 1e-3 in
    round 0, before any mixing matters (the local phase amplifies
    layout-dependent rounding); with two they agree to 6e-8 while the
    workers sit 1.6e-2 apart (measured, PERF.md PR 21)."""
    cfg = get_preset(HEADLINE)
    return cfg.replace(
        gossip=dataclasses.replace(cfg.gossip, local_ep=1),
        data=dataclasses.replace(cfg.data, num_users=8,
                                 synthetic_train_size=2048,
                                 synthetic_test_size=512))


def legs(n_devices: int) -> dict:
    """name -> thunk, in running order."""
    head = ["--preset", HEADLINE, "--rounds", "2"]
    table = {
        "gossip": lambda: leg_cli(["--preset", "baseline5", "--rounds", "3"]),
        "federated": lambda: leg_cli(["--preset", "baseline3",
                                      "--rounds", "3"]),
        "scatter": lambda: leg_cli([*head, "--set",
                                    "gossip.update_sharding=scatter"]),
        # Prefetch stages block b+1 while block b runs: two blocks.
        "prefetch": lambda: leg_cli(["--preset", HEADLINE, "--rounds", "4",
                                     "--set", "gossip.prefetch=on",
                                     "--set", "gossip.block_rounds=2"]),
        "fused": lambda: leg_fused(
            get_preset(HEADLINE),
            [*head, "--set", "gossip.fused_update=on",
             "--set", "mesh_devices=1"]),
        "codec": lambda: leg_codec(get_preset(HEADLINE)),
        "trace": lambda: leg_trace(["--preset", HEADLINE, "--rounds", "1"],
                                   need_device_time=True),
        "consensus": leg_consensus,
    }
    if n_devices >= 4:
        table["multichip-baseline5"] = lambda: leg_baseline5_on(4)
        table["multichip-ring"] = lambda: leg_ring_parity(ring8_cfg())
        table["multichip-ring-scatter"] = lambda: leg_ring_parity(
            ring8_cfg(), update_sharding="scatter")
    return table


def run_legs(table: dict, meter: CompileMeter) -> list[str]:
    """Run every leg, print its JSON line, return the names that failed
    (a failing leg never stops the others: one chip call, all the news)."""
    failed = []
    for name, thunk in table.items():
        c0, h0, t0 = meter.compile_s, meter.cache_hits, time.perf_counter()
        line = {"leg": name, **device_info()}
        try:
            line.update(thunk(), ok=True)
        except Exception as e:   # the boundary: report, go on, exit 1
            traceback.print_exc()
            line.update(ok=False, error=f"{type(e).__name__}: {e}"[:500])
            failed.append(name)
        wall = time.perf_counter() - t0
        comp = meter.compile_s - c0
        line.update(compile_s=round(comp, 1),
                    steady_s=round(max(wall - comp, 0.0), 1),
                    cache_hits=meter.cache_hits - h0)
        print(json.dumps(line), flush=True)
    return failed


def main(argv: list[str] | None = None) -> int:
    names = list(sys.argv[1:] if argv is None else argv)
    cache_dir = enable_compile_cache()
    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX's default backend is "
              f"{backend!r} ({jax.devices()})", file=sys.stderr)
        return 1
    print(f"# devices: {jax.devices()}", flush=True)
    print(f"# compile cache: {cache_dir}", flush=True)
    info = device_info()
    table = legs(info["n_devices"])
    unknown = [n for n in names if n not in table]
    if unknown:
        print(f"chip_smoke: unknown leg(s) {unknown}; one of {list(table)}",
              file=sys.stderr)
        return 2
    if names:
        table = {n: table[n] for n in names}
    with CompileMeter() as meter:
        failed = run_legs(table, meter)
    if info["n_devices"] < 4:
        print(f"# multichip: skipped ({info['n_devices']} device)",
              flush=True)
    if failed:
        print(json.dumps({"ok": False, "failed": failed}))
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": info["platform"], "kind": info["device_kind"],
        "count": info["n_devices"]}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
