"""Multi-host distributed backend: DCN × ICI hybrid meshes.

The reference has NO communication backend at all — its "multi-node"
story is N objects in one Python process (SURVEY §2.4).  dopt's
equivalent of a NCCL/MPI launcher is the jax runtime itself:

* ``initialize_distributed()`` wires ``jax.distributed`` from standard
  cluster environment variables (one call per host process; afterwards
  ``jax.devices()`` spans every host and collectives ride ICI within a
  slice and DCN across slices).
* ``make_hybrid_mesh()`` builds a 2-D ``Mesh`` with a slow outer axis
  (``hosts`` — DCN) and a fast inner axis (``ici``), so shardings can
  keep bandwidth-hungry collectives on ICI.
* the generic ``dopt.parallel.mesh.worker_sharding`` folds the engine's
  single logical worker axis over BOTH mesh axes (workers = hosts × ici
  lanes): neighboring workers land on the same slice, which means
  ring/dynamic gossip topologies cross DCN only at slice boundaries —
  exactly 2 of N edges for a ring, the minimum possible.

Single-process this degrades gracefully: ``initialize_distributed`` is a
no-op without cluster env vars, and the hybrid mesh reshapes the local
devices, which is also how the 8-virtual-CPU-device tests exercise the
full multi-host code path without a cluster (SURVEY §4's answer to
"test distributed without one").
"""

from __future__ import annotations

import json
import os
import socket
import time
from pathlib import Path

import jax
import numpy as np
from jax.sharding import Mesh

HOST_AXIS = "hosts"   # slow axis: crosses DCN on a real multi-slice job
ICI_AXIS = "ici"      # fast axis: stays on-slice


def pick_ephemeral_port(host: str = "127.0.0.1") -> int:
    """Bind port 0, read back the kernel's choice, release it."""
    with socket.socket() as s:
        s.bind((host, 0))
        return s.getsockname()[1]


def write_handoff(path: str | Path, address: str) -> None:
    """Publish the coordinator address atomically (tmp + rename): a
    waiter never reads a half-written file."""
    path = Path(path)
    path.parent.mkdir(parents=True, exist_ok=True)
    tmp = path.with_name(path.name + ".tmp")
    tmp.write_text(json.dumps({"coordinator": address}))
    os.replace(tmp, path)


def wait_handoff(path: str | Path, *, poll_s: float = 0.05,
                 max_polls: int = 2400) -> str:
    """Poll until the handoff file appears; returns the coordinator
    address.  Bounded by poll COUNT (default ~2 minutes at 50 ms) so
    an orphaned waiter fails loudly instead of hanging forever."""
    path = Path(path)
    for _ in range(max_polls):
        if path.exists():
            try:
                return str(json.loads(path.read_text())["coordinator"])
            except (ValueError, KeyError):
                pass   # racing the rename of a stale tmp: retry
        time.sleep(poll_s)
    raise TimeoutError(
        f"no coordinator handoff at {path} after {max_polls} polls "
        "(did process 0 die before binding?)")


def bootstrap_child_backend(handoff_path: str | Path, process_id: int,
                            num_processes: int, devices_per_proc: int, *,
                            host: str = "127.0.0.1",
                            collectives: str = "gloo") -> str:
    """The ONE fleet-child jax bootstrap, shared by
    ``scripts/multiprocess_demo.py`` and ``python -m dopt.serve``:
    REPLACE any inherited virtual-device-count flag (test harnesses
    export their own N and last-one-wins is not contractual), pin the
    CPU platform + collectives implementation before the backend
    initialises, rendezvous on the port-0 handoff coordinator, wire
    ``jax.distributed``, and sanity-check the resulting process/device
    topology.  Returns the coordinator address.  Must run before
    anything touches a jax backend in this process."""
    import re

    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   os.environ.get("XLA_FLAGS", ""))
    os.environ["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count="
        f"{devices_per_proc}")
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_cpu_collectives_implementation", collectives)
    address = coordinator_handoff(handoff_path, process_id, host=host)
    if not initialize_distributed(address, num_processes, process_id):
        raise RuntimeError(
            "initialize_distributed returned False with explicit args")
    if jax.process_count() != num_processes:
        raise RuntimeError(
            f"expected {num_processes} processes, backend reports "
            f"{jax.process_count()}")
    if jax.local_device_count() != devices_per_proc:
        raise RuntimeError(
            f"expected {devices_per_proc} local devices, backend "
            f"reports {jax.local_device_count()}")
    return address


def coordinator_handoff(path: str | Path, process_id: int, *,
                        host: str = "127.0.0.1",
                        poll_s: float = 0.05,
                        max_polls: int = 2400) -> str:
    """Ephemeral-port coordinator bootstrap for multi-process CPU
    fleets: process 0 picks a port-0 ephemeral port IN ITS OWN PROCESS
    and publishes ``host:port`` through an atomic handoff file; every
    other process waits on the file.  This replaces the parent-probed
    fixed-port scheme whose bind raced everything on the machine for
    the whole child-interpreter startup (seconds) — the remaining
    TOCTOU window is the microseconds between the probe socket closing
    and the coordinator's gRPC server binding, inside one process."""
    path = Path(path)
    if int(process_id) == 0:
        address = f"{host}:{pick_ephemeral_port(host)}"
        write_handoff(path, address)
        return address
    return wait_handoff(path, poll_s=poll_s, max_polls=max_polls)


def initialize_distributed(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> bool:
    """Initialise ``jax.distributed`` for a multi-host job.

    Explicit args win; otherwise standard env vars are used
    (``JAX_COORDINATOR_ADDRESS``/``JAX_NUM_PROCESSES``/``JAX_PROCESS_ID``,
    or the TPU-pod metadata jax autodetects).  Returns True if the
    distributed runtime was (or already is) initialised, False when
    nothing indicates a multi-process job (single-host: no-op).
    """
    coordinator_address = coordinator_address or os.environ.get(
        "JAX_COORDINATOR_ADDRESS")
    if num_processes is None and os.environ.get("JAX_NUM_PROCESSES"):
        num_processes = int(os.environ["JAX_NUM_PROCESSES"])
    if process_id is None and os.environ.get("JAX_PROCESS_ID"):
        process_id = int(os.environ["JAX_PROCESS_ID"])
    if coordinator_address is None and num_processes is None:
        return False
    if jax.distributed.is_initialized():
        return True   # a launcher/framework already wired the runtime
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
    return True


def make_hybrid_mesh(num_hosts: int | None = None, *, devices=None) -> Mesh:
    """2-D (hosts × ici) mesh.

    On a real multi-host job ``num_hosts`` defaults to
    ``jax.process_count()`` and rows follow device locality (each row =
    one host's devices, so the inner axis is pure ICI).  Single-process,
    ``num_hosts`` partitions the local devices into virtual hosts —
    bit-identical program, no cluster needed.
    """
    if devices is None:
        devices = jax.devices()
    if num_hosts is None:
        num_hosts = max(jax.process_count(), 1)
    n = len(devices)
    if n % num_hosts:
        raise ValueError(f"{n} devices not divisible into {num_hosts} hosts")
    per_host = n // num_hosts
    # jax.devices() orders by process index first, so a row-major reshape
    # groups each host's devices into one row.
    grid = np.asarray(devices).reshape(num_hosts, per_host)
    return Mesh(grid, (HOST_AXIS, ICI_AXIS))


def dcn_edge_count(w_matrix: np.ndarray, num_hosts: int) -> int:
    """Diagnostic: how many nonzero mixing-matrix edges cross a host
    (DCN) boundary under the contiguous worker→host fold.  A ring over
    H hosts should report exactly 2·H·(H>1) directed crossings; dense
    graphs report O(N²·(1−1/H)) — use it to pick topologies that keep
    gossip on ICI."""
    n = w_matrix.shape[0]
    if n % num_hosts:
        raise ValueError(f"{n} workers not divisible into {num_hosts} hosts")
    per = n // num_hosts
    host_of = np.arange(n) // per
    i, j = np.nonzero(w_matrix)
    return int(np.sum(host_of[i] != host_of[j]))
