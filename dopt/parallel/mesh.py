"""Device mesh and worker-axis sharding.

The engine's whole layout hinges on one idea (SURVEY §7): the reference's
N sequentially-stepped client objects become ONE stacked pytree with a
leading ``workers`` axis, sharded over a 1-D ``jax.sharding.Mesh``.
``num_workers`` need not equal the device count: workers fold onto
devices (``workers = devices × workers_per_device``) and per-device
lanes are vmapped — that is how 32 workers run on a v5e-8
(mesh plan "(cores=8, workers_per_core=4)").
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

WORKER_AXIS = "workers"


def make_mesh(num_devices: int | None = None, *, devices=None) -> Mesh:
    """1-D mesh over the worker axis."""
    if devices is None:
        devices = jax.devices()
    if num_devices is not None:
        if len(devices) < num_devices:
            raise ValueError(f"need {num_devices} devices, have {len(devices)}")
        devices = devices[:num_devices]
    return Mesh(np.asarray(devices), (WORKER_AXIS,))


def fit_mesh_devices(num_workers: int, requested: int | None = None) -> int:
    """Largest device count <= min(workers, available) that divides the
    worker count evenly (workers fold onto devices in equal lanes)."""
    avail = len(jax.devices()) if requested is None else requested
    d = min(num_workers, avail)
    while num_workers % d:
        d -= 1
    return d


def worker_axes(mesh: Mesh) -> tuple[str, ...]:
    """The mesh axis names the logical worker axis folds over: just
    ``workers`` on a 1-D mesh, ``(hosts, ici)`` on a hybrid mesh
    (dopt.parallel.multihost)."""
    return tuple(mesh.axis_names)


def worker_sharding(mesh: Mesh) -> NamedSharding:
    """Shard the leading (worker) axis across ALL mesh axes; everything
    else replicated within a worker shard."""
    return NamedSharding(mesh, P(worker_axes(mesh)))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def shard_worker_tree(tree, mesh: Mesh):
    """Place a stacked [W, ...] pytree with the worker axis sharded.

    W must divide evenly by the mesh size (pad the worker count or pick
    a divisor worker total — the engine validates this upstream).

    On a multi-process fleet (``dopt serve``) the placement goes
    through ``make_array_from_callback``: every process holds the FULL
    host array (checkpoint restores read the same file), so each can
    slice out its addressable shards locally — zero collectives.  A
    bare ``device_put`` against a non-addressable sharding would run a
    cross-process ``assert_equal`` broadcast PER LEAF, a pile of tiny
    gloo collectives on the restore path that the tcp transport's
    message-interleave race loves."""
    sh = worker_sharding(mesh)
    multiprocess = jax.process_count() > 1

    def put(x):
        if x.shape[0] % mesh.size:
            raise ValueError(
                f"worker axis {x.shape[0]} not divisible by mesh size {mesh.size}"
            )
        if multiprocess:
            x = np.asarray(x)
            return jax.make_array_from_callback(
                x.shape, sh, lambda idx: x[idx])
        return jax.device_put(x, sh)

    return jax.tree.map(put, tree)


def shard_over_workers(fn, mesh: Mesh, in_specs, out_specs):
    """``shard_map`` a stacked-worker function over the mesh.

    Specs are strings with one character per argument/output — ``w``
    (leading worker axis sharded over all mesh axes) or ``r``
    (replicated); each character acts as a pytree prefix for its
    argument.  A single-character string means ONE spec (e.g. an
    evaluator returning a metrics dict uses out_specs="w").  Used by
    the engines to run the grouped stacked-forward local phase as pure
    per-device computation (workers are independent — zero
    collectives), which also keeps the worker-in-channels grouped conv
    out of the SPMD partitioner's hands (it cannot split that conv's
    feature groups itself).
    """
    w_, r_ = P(worker_axes(mesh)), P()

    def one(c):
        if c == "w":
            return w_
        if c == "r":
            return r_
        raise ValueError(f"spec characters are 'w' or 'r', got {c!r}")

    def resolve(spec):
        if len(spec) == 1:
            return one(spec)
        return tuple(one(c) for c in spec)

    return jax.shard_map(fn, mesh=mesh, in_specs=resolve(in_specs),
                         out_specs=resolve(out_specs), check_vma=False)


def make_worker_mesh(num_workers: int, mesh_devices: int | None = None,
                     mesh_hosts: int | None = None) -> Mesh:
    """The engines' mesh factory: 1-D worker mesh by default, 2-D
    (hosts × ici) hybrid mesh when ``mesh_hosts`` is set
    (dopt.parallel.multihost)."""
    if not mesh_hosts:
        return make_mesh(fit_mesh_devices(num_workers, mesh_devices))

    from dopt.parallel.multihost import make_hybrid_mesh

    devices = jax.devices()
    if jax.process_count() > 1:
        # On a real multi-controller job every process's devices must be
        # in the mesh, and slicing would break the host-row alignment
        # make_hybrid_mesh relies on — use all devices or nothing.
        n = len(devices)
        if mesh_devices not in (None, n):
            raise ValueError(
                f"multi-host jobs must use all {n} devices "
                f"(got mesh_devices={mesh_devices})")
        if num_workers % n:
            raise ValueError(
                f"{num_workers} workers do not fold evenly onto the "
                f"{n} devices of this multi-host job")
        return make_hybrid_mesh(mesh_hosts, devices=devices)

    # Single process (incl. virtual-host testing): largest device count
    # that divides the workers AND splits evenly into the virtual hosts.
    avail = len(devices) if mesh_devices is None else mesh_devices
    d = min(num_workers, avail)
    while d > 0 and (num_workers % d or d % mesh_hosts):
        d -= 1
    if d <= 0:
        raise ValueError(
            f"no device count <= {avail} folds {num_workers} workers "
            f"onto {mesh_hosts} hosts")
    return make_hybrid_mesh(mesh_hosts, devices=devices[:d])
