"""Real multi-process ``jax.distributed`` execution — the launcher leg.

The reference has no communication backend at all (SURVEY §2.4: its
"multi-node" story is N objects in one process).  dopt's backend is the
jax runtime: ``dopt.parallel.multihost.initialize_distributed`` wires
the coordinator, and the hybrid (hosts × ici) mesh lays workers out so
gossip edges stay on the fast axis.  Everything below the mesh is
identical single- or multi-process — this script proves it by actually
running the same GossipTrainer round in N OS processes against one
coordination service and asserting every process converges to the SAME
trajectory (the determinism the in-process tests pin, now across a real
process boundary with gloo CPU collectives standing in for ICI/DCN).

Parent mode (default): spawns N children of this script sharing a
coordinator HANDOFF file, collects their output, and checks they all
report the same final metrics.  Child mode (``--process-id I``)
self-organises the coordinator: child 0 binds a port-0 ephemeral port
in its own process and publishes ``host:port`` through the handoff
file (atomic rename), the others wait on it — no parent-probed fixed
port, so the bind race window shrinks from the whole child-interpreter
startup to microseconds inside one process
(``dopt.parallel.multihost.coordinator_handoff``).

Usage:
    python scripts/multiprocess_demo.py                # 2 procs × 4 devices
    python scripts/multiprocess_demo.py --num-processes 2 --rounds 2
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
OK_MARK = "MULTIPROC-ROUND-OK"


def child_main(args) -> int:
    # Platform + virtual-device setup must precede backend init; the
    # whole dance (flag replace, gloo pin, handoff rendezvous,
    # jax.distributed init, topology asserts) is the shared
    # bootstrap_child_backend — ONE implementation for this demo and
    # the dopt.serve fleet children.
    sys.path.insert(0, str(REPO))
    from dopt.parallel.multihost import HOST_AXIS, bootstrap_child_backend
    from dopt.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    bootstrap_child_backend(args.handoff, args.process_id,
                            args.num_processes, args.devices_per_proc)
    import jax

    assert jax.device_count() == args.num_processes * args.devices_per_proc

    from dopt.config import (DataConfig, ExperimentConfig, GossipConfig,
                             ModelConfig, OptimizerConfig)
    from dopt.engine import GossipTrainer

    num_workers = jax.device_count()
    cfg = ExperimentConfig(
        name="multiproc-demo", seed=3,
        data=DataConfig(dataset="synthetic", num_users=num_workers,
                        synthetic_train_size=32 * num_workers,
                        synthetic_test_size=64),
        model=ModelConfig(model="mlp", input_shape=(28, 28, 1),
                          faithful=False),
        optim=OptimizerConfig(lr=0.1, momentum=0.5),
        gossip=GossipConfig(algorithm="dsgd", topology="circle",
                            mode="metropolis", local_ep=1, local_bs=8),
        mesh_hosts=args.num_processes,
    )
    tr = GossipTrainer(cfg)
    assert tr.mesh.shape[HOST_AXIS] == args.num_processes, tr.mesh
    h = tr.run(rounds=args.rounds)
    acc = h.last().get("avg_test_acc")
    loss = h.last().get("avg_train_loss")
    print(f"[p{args.process_id}] {OK_MARK} procs={args.num_processes} "
          f"mesh={dict(tr.mesh.shape)} rounds={args.rounds} "
          f"acc={acc:.6f} train_loss={loss:.6f}", flush=True)
    return 0


def parent_main(args) -> int:
    # Child 0 picks its own ephemeral port and hands it off through a
    # file, so the historical parent-probe TOCTOU is gone; the retry
    # loop stays for the one remaining non-dopt flake — gloo's tcp
    # transport interleaving two collectives' messages under host load.
    diag = ""
    for attempt in range(3):
        rc, diag = _parent_attempt(args)
        if rc != 3:  # 3 = retryable (residual bind race / gloo transport)
            return rc
        print(f"retryable launch failure (attempt {attempt + 1}/3), "
              "respawning with a fresh coordinator handoff",
              file=sys.stderr)
    # Out of retries: surface the last attempt's child output so a
    # non-retryable failure that happened to match the heuristics is
    # still diagnosable from the logs.
    sys.stderr.write(f"--- last attempt child output ---\n{diag}\n")
    print("FAIL: retryable launch failure persisted after 3 attempts",
          file=sys.stderr)
    return 1


def _parent_attempt(args) -> tuple[int, str]:
    import tempfile

    handoff = os.path.join(tempfile.mkdtemp(prefix="dopt-mpdemo-"),
                           "coordinator.json")
    # No env surgery here: each child's bootstrap_child_backend
    # REPLACES any inherited device-count flag itself.
    procs = [
        subprocess.Popen(
            [sys.executable, __file__, "--process-id", str(i),
             "--num-processes", str(args.num_processes),
             "--devices-per-proc", str(args.devices_per_proc),
             "--handoff", handoff, "--rounds", str(args.rounds)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True)
        for i in range(args.num_processes)
    ]
    outs, rcs = [], []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=args.timeout)
            outs.append(out)
            rcs.append(p.returncode)
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        print("TIMEOUT: children killed", file=sys.stderr)
        return 2, ""

    ok_lines = []
    for i, (rc, out) in enumerate(zip(rcs, outs)):
        marks = [ln for ln in out.splitlines() if OK_MARK in ln]
        ok_lines += marks
        if rc != 0 or not marks:
            low = out.lower()
            if "failed to bind" in low or "address already in use" in low:
                # Retryable: another process grabbed the probed port.
                # The caller prints this output if retries run out.
                return 3, out
            if "op.preamble.length" in low:
                # Retryable: gloo's tcp transport occasionally
                # interleaves two collectives' messages on one pair
                # under host load (preamble/buffer length mismatch →
                # SIGABRT).  A transport-layer race, not a dopt bug —
                # respawn the whole attempt on a fresh coordinator.
                # (Matched on the specific signature only: a generic
                # 'gloo' match would retry — and mask — deterministic
                # failures whose logs merely mention the transport.)
                return 3, out
            sys.stderr.write(f"--- child {i} (rc={rc}) output ---\n{out}\n")
            print(f"FAIL: child {i} rc={rc} ok={bool(marks)}", file=sys.stderr)
            return 1, out
        print(marks[0])

    # Determinism across the process boundary: every process must report
    # the identical trajectory (same metrics to the printed digit).
    metrics = {ln.split(OK_MARK, 1)[1] for ln in ok_lines}
    if len(metrics) != 1:
        print(f"FAIL: processes disagree: {sorted(metrics)}", file=sys.stderr)
        return 1, ""
    print(f"multiprocess demo OK: {args.num_processes} processes × "
          f"{args.devices_per_proc} devices, identical trajectories")
    return 0, ""


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--num-processes", type=int, default=2)
    ap.add_argument("--devices-per-proc", type=int, default=4)
    ap.add_argument("--rounds", type=int, default=2)
    ap.add_argument("--timeout", type=float, default=420.0)
    ap.add_argument("--process-id", type=int, default=None,
                    help="(internal) run as child with this process id")
    ap.add_argument("--handoff", default=None,
                    help="(internal) coordinator handoff file")
    args = ap.parse_args(argv)
    if args.process_id is not None:
        return child_main(args)
    return parent_main(args)


if __name__ == "__main__":
    raise SystemExit(main())
