"""Worker-axis collectives: gossip mixing and federated aggregation.

This module is the TPU-native replacement for the reference's implicit
"communication layer" (SURVEY §2.4): the server handing state_dict
copies to clients (``servers.py:59-64``) and ``Simulator.Neighbors``
passing live state_dict references between peers
(``simulators.py:91-97`` + ``clients.py:61-69``).

Two execution paths for the consensus step  x_i ← Σ_j W_ij x_j :

* ``mix_dense`` — one ``tensordot`` of the [n, n] mixing matrix against
  the stacked [W, ...] pytree, written in the global view.  Under jit
  with the worker axis sharded, XLA's SPMD partitioner lowers this to
  ``all_gather`` over ICI + a local contraction — the right choice for
  complete/random/arbitrary graphs (the matrix is data, not code).
* ``mix_shifts_shardmap`` — explicit ``shard_map`` + ``lax.ppermute``
  per circulant diagonal of W (from ``dopt.topology.shift_decomposition``).
  For banded topologies (ring, dynamic single-edge) this moves only the
  neighbor shards that are actually needed: O(k·|θ|) bytes over ICI
  instead of O(n·|θ|) for the all_gather, where k = number of nonzero
  diagonals (ring: 2).

``masked_average`` is the federated path: uniform state averaging over
the sampled-client set (``servers.py:42-48``) as one weighted
reduce-sum over the worker axis, with partial participation as a 0/1
mask instead of Python-side client selection.
"""

from __future__ import annotations

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from dopt.parallel.mesh import WORKER_AXIS

# Every [n, n] worker-axis contraction of f32 state runs at full f32
# precision.  A TPU multiplies f32 operands in bf16 passes unless told
# otherwise: bf16(1/3)·3 = 1.00195, so a "doubly stochastic" mix at the
# default precision moved every leaf's worker-mean by ~8e-3 per round
# and rounded the parameters themselves to bf16 (measured on a v5e,
# PERF.md PR 21).  These contractions are HBM-bound (n ≤ a few dozen
# rows), so the extra MXU passes are free; convolutions keep the
# default.  A no-op on the CPU.
MIX_PRECISION = jax.lax.Precision.HIGHEST


def mix_dense(stacked, w_matrix, mesh: Mesh | None = None,
              comm_dtype=None):
    """x_i ← Σ_j W_ij x_j for every leaf of a stacked [W, ...] pytree.

    Global-view formulation; XLA inserts the collectives when the worker
    axis is sharded.  ``w_matrix`` may be [n, n] or a scalar-weighted
    stack already selected for the round.  Pass ``mesh`` to pin the
    output back onto the worker axis (XLA otherwise may choose to
    replicate the contraction result).

    ``comm_dtype`` (e.g. ``jnp.bfloat16``) is WIRE-ONLY compression:
    shards are narrowed just for the cross-device gather (halving
    ICI/DCN bytes at bf16) and everything else stays exact — the mixing
    matrix remains float32 (bf16 would break row-stochasticity by
    ~1e-3/row and compound over rounds) and the accumulation runs in
    float32.  Requires ``mesh`` (without a mesh nothing crosses a wire,
    so there is nothing to compress — it raises to avoid a silent
    no-op)."""
    w = jnp.asarray(w_matrix, dtype=jnp.float32)
    if comm_dtype is not None:
        if mesh is None:
            raise ValueError("comm_dtype compression requires a mesh")
        return _mix_dense_compressed(stacked, w, mesh, comm_dtype)

    def mix_leaf(x):
        y = jnp.tensordot(w.astype(x.dtype), x, axes=[[1], [0]],
                          precision=MIX_PRECISION)
        y = y.astype(x.dtype)
        if mesh is not None:
            from dopt.parallel.mesh import worker_sharding

            y = jax.lax.with_sharding_constraint(y, worker_sharding(mesh))
        return y

    # dopt_mix scope: phase attribution for the profiler's
    # conv/comm/update split (dopt.utils.profiling.classify_phase).
    with jax.named_scope("dopt_mix"):
        return jax.tree.map(mix_leaf, stacked)


def _mix_dense_compressed(stacked, w, mesh: Mesh, comm_dtype):
    """Wire-only compressed dense mixing as an explicit shard_map: each
    device all-gathers the OTHER workers' shards at ``comm_dtype`` (the
    only bytes that cross ICI/DCN), then contracts its f32 mixing-matrix
    rows against the f32-upcast gather — exact W, f32 accumulation,
    narrow wire."""
    from dopt.parallel.mesh import worker_axes

    ax = worker_axes(mesh)

    def per_device(wr, xl):
        # wr: [W/D, W] f32 rows; xl: [W/D, ...] local worker shard.
        xg = jax.lax.all_gather(xl.astype(comm_dtype), ax, axis=0,
                                tiled=True)
        y = jnp.tensordot(wr, xg.astype(jnp.float32), axes=[[1], [0]],
                          precision=MIX_PRECISION)
        return y.astype(xl.dtype)

    def mix_leaf(x):
        fn = jax.shard_map(per_device, mesh=mesh,
                           in_specs=(P(ax, None), P(ax)),
                           out_specs=P(ax))
        return fn(w, x)

    return jax.tree.map(mix_leaf, stacked)


def _shift_plan(shift_ids, lanes: int, num_devices: int):
    """Static routing plan for the folded shift path.

    Returns ``(plan, ship)`` where ``plan[k] = (q0, q1, r)`` decomposes
    global shift ``shift_ids[k]`` into its device rotations and lane
    offset, and ``ship[q]`` is the sorted list of source lanes that must
    actually travel for nonzero rotation q — the union over consuming
    shifts, NOT the whole lane block.  A straddling ring shift (r ≠ 0)
    needs only ``lanes − r`` lanes from rotation q and ``r`` from q+1,
    so e.g. the 32-worker ring on 8 devices ships 2 lane-shards per
    device per round instead of 8 full blocks.

    Contiguity invariant used by ``mix_shifts``: every consumer needs a
    contiguous lane range [a, b), and since ship[q] ⊇ [a, b) is a sorted
    list of distinct lanes, that range occupies contiguous positions in
    the shipped block.
    """
    plan: list[tuple[int, int, int]] = []
    need: dict[int, set[int]] = {}
    for s in shift_ids:
        q, r = divmod(int(s), lanes)
        q0, q1 = q % num_devices, (q + 1) % num_devices
        plan.append((q0, q1, r))
        if r == 0:
            if q0 != 0:
                need.setdefault(q0, set()).update(range(lanes))
        else:
            if q0 != 0:
                need.setdefault(q0, set()).update(range(r, lanes))
            if q1 != 0:
                need.setdefault(q1, set()).update(range(r))
    ship = {q: sorted(v) for q, v in need.items()}
    return plan, ship


def device_rotations(shift_ids, lanes: int, num_devices: int) -> tuple[int, ...]:
    """The nonzero device-level ring rotations (one ``lax.ppermute``
    each) the folded shift path needs for a global circulant shift set:
    shift s = q·lanes + r touches rotation q (and q+1 when r ≠ 0)."""
    _, ship = _shift_plan(shift_ids, lanes, num_devices)
    return tuple(sorted(ship))


def shift_comm_lanes(shift_ids, lanes: int, num_devices: int) -> int:
    """Total worker-lane shards each device ships per ``mix_shifts``
    call — the shift path's ICI byte cost in units of |θ|-sized lanes,
    which the engine's 'auto' heuristic compares against the dense
    all_gather's (n − lanes) remote lanes per device."""
    _, ship = _shift_plan(shift_ids, lanes, num_devices)
    return sum(len(v) for v in ship.values())


def mix_shifts(stacked, shift_ids, coeff_table, mesh: Mesh, comm_dtype=None):
    """Explicit ICI path: x_i ← Σ_s coeff_s[i] · x_{(i+s) mod n}.

    ``shift_ids`` is the STATIC tuple of circulant shifts (compiled into
    the program); ``coeff_table`` is the per-round [k, n] float32
    coefficient DATA (``dopt.topology.coeffs_for_matrix``), so
    time-varying schedules and dropout-repaired matrices reuse one
    compiled step.

    Workers fold onto devices in L = n / mesh.size contiguous lanes
    (worker i = device i//L, lane i%L — the ``shard_worker_tree``
    layout).  The [n, n] circulant then decomposes into DEVICE-level
    ring rotations plus a static lane slice: global shift s = q·L + r
    needs lanes r..L-1 from device d+q and, when r ≠ 0, lanes 0..r-1
    from device d+q+1.  Each nonzero rotation is ONE ``lax.ppermute``
    carrying only the union of lanes its consumers need (``_shift_plan``)
    — a folded ring ships 2 single-lane shards per device per round
    (e.g. 32 workers on a v5e-8, SURVEY §7's "cores=8, workers_per_core=4"
    plan) instead of the dense path's (n − L)-lane all_gather.  L = 1
    degenerates to the classic one-rotation-per-shift ring schedule.
    """
    D = mesh.size
    shift_ids = tuple(int(s) for s in shift_ids)
    coeff_table = jnp.asarray(coeff_table, dtype=jnp.float32)
    n = coeff_table.shape[1]
    if n % D:
        raise ValueError(f"{n} workers do not fold onto {D} devices evenly")
    L = n // D
    plan, ship = _shift_plan(shift_ids, L, D)
    # Shipped-block bookkeeping: lane a of rotation q sits at position
    # pos[q][a] in that rotation's payload; contiguous source ranges
    # stay contiguous (see _shift_plan docstring).
    pos = {q: {lane: i for i, lane in enumerate(lanes_q)}
           for q, lanes_q in ship.items()}

    def per_device(coeffs, x):
        # x: [L, ...] local lane block; coeffs: [k, L] this block's weights.
        # comm_dtype narrows the payload only for the ppermute hops (the
        # bytes on the wire); lane values that never cross a wire (the
        # q == 0 contributions, incl. the shift-0 self term) stay exact,
        # and accumulation stays at the leaf dtype.
        xc = x.astype(comm_dtype) if comm_dtype is not None else x
        blocks = {}
        for q, lanes_q in ship.items():
            payload = xc if len(lanes_q) == L else xc[np.asarray(lanes_q)]
            perm = [((d + q) % D, d) for d in range(D)]
            blocks[q] = jax.lax.ppermute(payload, WORKER_AXIS,
                                         perm).astype(x.dtype)

        def part(q, a, b):
            """Lanes [a, b) sourced from rotation q (0 = local/exact)."""
            if q == 0:
                return x[a:b]
            p = pos[q][a]
            return blocks[q][p:p + (b - a)]

        acc = jnp.zeros_like(x)
        for k, (q0, q1, r) in enumerate(plan):
            if r == 0:
                contrib = part(q0, 0, L)
            else:
                contrib = jnp.concatenate([part(q0, r, L), part(q1, 0, r)],
                                          axis=0)
            c = coeffs[k].reshape((L,) + (1,) * (x.ndim - 1)).astype(x.dtype)
            acc = acc + c * contrib
        return acc

    coeff_specs = P(None, WORKER_AXIS)  # [k, n] -> coeffs sharded on worker axis

    def mix_leaf(x):
        fn = jax.shard_map(
            per_device,
            mesh=mesh,
            in_specs=(coeff_specs, P(WORKER_AXIS)),
            out_specs=P(WORKER_AXIS),
        )
        return fn(coeff_table, x)

    with jax.named_scope("dopt_mix"):
        return jax.tree.map(mix_leaf, stacked)


def mix_shifts_shardmap(stacked, shifts, mesh: Mesh, comm_dtype=None):
    """``mix_shifts`` with the shifts-and-coefficients pairing of
    ``dopt.topology.shift_decomposition`` (``[(shift, coeffs[n]), ...]``)
    — the single-matrix convenience form."""
    return mix_shifts(stacked, [s for s, _ in shifts],
                      jnp.asarray([c for _, c in shifts], dtype=jnp.float32),
                      mesh, comm_dtype)


def where_mask(mask, a, b):
    """Per-worker select over stacked pytrees: mask[i] ? a_i : b_i.
    Used for client-sampling (federated) and worker-dropout (gossip)
    participation masks."""
    def sel(x, y):
        m = mask.reshape((-1,) + (1,) * (x.ndim - 1)).astype(bool)
        return jnp.where(m, x, y)
    return jax.tree.map(sel, a, b)


def masked_average(stacked, mask, mesh: Mesh | None = None, comm_dtype=None):
    """Uniform average of the masked workers' states, replicated back to
    every worker: theta ← Σ_i m_i x_i / Σ_i m_i  (reference
    ``average_weights``, servers.py:42-48, with client sampling as data).

    Returns a pytree WITHOUT the worker axis (the global model).

    ``comm_dtype`` (requires ``mesh``) is wire-only compression of the
    aggregation, mirroring ``mix_dense``: each device reduces its local
    lanes at full precision, only the per-device PARTIAL sums cross the
    wire at the narrow dtype (one psum), and the final divide runs at
    the leaf dtype."""
    m = jnp.asarray(mask, dtype=jnp.float32)
    denom = jnp.maximum(m.sum(), 1.0)
    if comm_dtype is not None:
        if mesh is None:
            raise ValueError("comm_dtype compression requires a mesh")
        return _masked_average_compressed(stacked, m, denom, mesh, comm_dtype)

    def avg_leaf(x):
        mm = m.reshape((-1,) + (1,) * (x.ndim - 1)).astype(x.dtype)
        return (x * mm).sum(axis=0) / denom.astype(x.dtype)

    with jax.named_scope("dopt_mix"):
        return jax.tree.map(avg_leaf, stacked)


def mean_weight_matrix(mask):
    """The masked-mean reduce as a [W, W] contraction matrix: every row
    is mask / max(Σ mask, 1), so W_mean @ X computes ``masked_average``
    broadcast back over the worker axis (each output row is the same
    global mean).  An all-dead mask yields the zero matrix — the
    contraction contributes nothing and the caller's passthrough term
    keeps theta.  Feeds the fused epilogue (``dopt.ops.fused_mix_update``
    under ``FederatedConfig.fused_update="on"``), which needs the mean
    expressed as a mixing-matrix contraction over the flat buckets."""
    m = jnp.asarray(mask, dtype=jnp.float32).reshape(-1)
    denom = jnp.maximum(m.sum(), 1.0)
    return jnp.broadcast_to(m / denom, (m.shape[0], m.shape[0]))


def _masked_average_compressed(stacked, m, denom, mesh: Mesh, comm_dtype):
    """Wire-only compressed federated reduce: each device sums its local
    lanes at full precision, the narrow PARTIAL sums are all-gathered
    (the only bytes on the wire), and the cross-device accumulation runs
    in float32 locally — so exactly one quantization per partial, never
    a narrow-dtype summation chain that would grow error with device
    count (mirrors ``_mix_dense_compressed``'s semantics)."""
    from dopt.parallel.mesh import worker_axes

    ax = worker_axes(mesh)

    def avg_leaf(x):
        def per_device(mask_l, x_l):
            mm = mask_l.reshape((-1,) + (1,) * (x_l.ndim - 1))
            part = (x_l.astype(jnp.float32) * mm).sum(axis=0)
            parts = jax.lax.all_gather(part.astype(comm_dtype), ax)
            tot = parts.astype(jnp.float32).sum(axis=0)
            return (tot / denom).astype(x_l.dtype)

        # all_gather+local-sum yields a value that IS replicated but
        # can't be statically proven so (unlike psum); skip the static
        # varying-axes check for this one collective.
        fn = jax.shard_map(per_device, mesh=mesh,
                           in_specs=(P(ax), P(ax)), out_specs=P(),
                           check_vma=False)
        return fn(m, x)

    return jax.tree.map(avg_leaf, stacked)


# ---------------------------------------------------------------------
# Sharded weight-update / consensus hot path (update_sharding="scatter")
# ---------------------------------------------------------------------
# "Automatic Cross-Replica Sharding of Weight Update in Data-Parallel
# Training" (Xu et al., arXiv:2004.13336) applied to the consensus
# round: instead of every lane's device redundantly materialising and
# post-processing the FULL |θ| during the mixing/aggregation phase, the
# parameter tree is flattened once into size-bounded f32/bf16 BUCKETS
# ([W, Fb] slabs), the cross-worker contraction runs as per-device
# partial sums + ``psum_scatter`` (each device produces only the 1/D
# shard it owns), the remaining update math runs on that shard, and ONE
# all-gather restores the full view.  Issuing the collectives bucket by
# bucket is what lets XLA's latency-hiding scheduler overlap bucket b's
# wire time with bucket b+1's compute (the scheduler and async
# collective fusion are libtpu defaults; no flag is set).


@dataclasses.dataclass(frozen=True)
class UpdateShardSpec:
    """Static flattening/bucketing plan for a stacked [W, ...] pytree.

    Built once at trainer construction (``make_update_shard_spec``);
    everything here is static python data so the bucket slicing compiles
    into the round program.  ``bounds`` are fold-aligned offsets into
    the zero-padded flat axis — every bucket's length divides evenly by
    ``fold`` (the mesh device count), which is what lets
    ``psum_scatter``/``all_gather`` split each bucket exactly."""

    treedef: object
    shapes: tuple[tuple[int, ...], ...]   # per-leaf shapes sans worker axis
    sizes: tuple[int, ...]
    dtype: object
    fold: int
    flat: int      # true flattened per-worker element count
    padded: int    # flat rounded up to a fold multiple
    bounds: tuple[int, ...]

    @property
    def num_buckets(self) -> int:
        return len(self.bounds) - 1


def make_update_shard_spec(tree, *, fold: int,
                           bucket_bytes: int = 4 << 20) -> UpdateShardSpec:
    """Plan the flat bucketing of ``tree`` (a stacked [W, ...] pytree).

    ``fold`` is the shard count (mesh size) every bucket must divide by;
    ``bucket_bytes`` bounds each bucket's per-worker payload so the
    mixing collectives are issued as a pipeline of comparable chunks
    rather than one monolithic transfer.  All leaves must share one
    dtype (the engines store params/momentum at a single param_dtype) —
    mixed dtypes would force a lossy common cast, so they are rejected."""
    leaves, treedef = jax.tree.flatten(tree)
    if not leaves:
        raise ValueError("cannot bucket an empty pytree")
    dtypes = {jnp.dtype(x.dtype) for x in leaves}
    if len(dtypes) != 1:
        raise ValueError(
            f"update sharding needs a uniform leaf dtype, got {dtypes}")
    dtype = dtypes.pop()
    shapes = tuple(tuple(x.shape[1:]) for x in leaves)
    sizes = tuple(int(np.prod(s)) if s else 1 for s in shapes)
    flat = int(sum(sizes))
    fold = max(int(fold), 1)
    padded = -(-flat // fold) * fold
    per_elem = dtype.itemsize
    step = max(int(bucket_bytes) // per_elem // fold, 1) * fold
    bounds = tuple(range(0, padded, step)) + (padded,)
    return UpdateShardSpec(treedef=treedef, shapes=shapes, sizes=sizes,
                           dtype=dtype, fold=fold, flat=flat,
                           padded=padded, bounds=bounds)


def stacked_to_buckets(tree, spec: UpdateShardSpec) -> list:
    """Flatten a stacked [W, ...] pytree into the spec's [W, Fb] bucket
    slabs (zero-padded tail).  The inverse is ``buckets_to_stacked`` —
    the round trip is bit-exact (pure reshape/concat/slice)."""
    leaves = jax.tree.leaves(tree)
    w = leaves[0].shape[0]
    flat = jnp.concatenate([x.reshape(w, -1) for x in leaves], axis=1)
    if spec.padded != spec.flat:
        flat = jnp.pad(flat, ((0, 0), (0, spec.padded - spec.flat)))
    return [flat[:, a:b] for a, b in zip(spec.bounds, spec.bounds[1:])]


def _flat_to_tree(flat, spec: UpdateShardSpec, lead: tuple[int, ...]):
    out, off = [], 0
    for shape, size in zip(spec.shapes, spec.sizes):
        out.append(flat[..., off:off + size].reshape(lead + shape))
        off += size
    return spec.treedef.unflatten(out)


def buckets_to_stacked(buckets: list, spec: UpdateShardSpec):
    flat = jnp.concatenate(buckets, axis=1)[:, :spec.flat]
    return _flat_to_tree(flat, spec, (flat.shape[0],))


def buckets_to_tree(buckets: list, spec: UpdateShardSpec):
    """Single (no worker axis) variant: [Fb] buckets → the θ tree."""
    flat = jnp.concatenate(buckets, axis=0)[:spec.flat]
    return _flat_to_tree(flat, spec, ())


def _require_flat_mesh(mesh: Mesh | None, what: str) -> str:
    if mesh is None:
        raise ValueError(f"{what} requires a mesh")
    if len(mesh.axis_names) != 1:
        raise ValueError(
            f"{what} runs psum_scatter over ONE worker axis; hybrid "
            f"(hosts × ici) meshes are not supported — got {mesh.shape}")
    return mesh.axis_names[0]


def mix_dense_scatter(buckets, w_matrix, mesh: Mesh, comm_dtype=None):
    """Reduce-scatter formulation of ``mix_dense`` over flat buckets:
    each device contracts the mixing matrix's columns for ITS lanes
    against its local [L, Fb] slab (a partial sum of the true output for
    every worker), and one ``psum_scatter`` both completes the sum and
    hands each device exactly its own lanes' mixed rows — no device
    ever materialises the [n, Fb] gathered fleet state, and the
    per-bucket issue order gives the latency-hiding scheduler chunks to
    overlap.

    Numerics: the mixing matrix and the accumulation stay FLOAT32
    regardless of the leaf dtype.  For f32 trees that differs from
    ``mix_dense`` only by summation association (the allclose-pinned
    parity contract); for bf16 trees it is strictly MORE precise than
    the dense path, which casts the matrix to bf16 and contracts at the
    leaf dtype — so bf16 scatter-vs-dense deltas include that matrix
    quantization (~1e-3/row), not just reassociation.

    ``comm_dtype`` narrows the PARTIAL sums for the ``psum_scatter``
    hop (the only bytes on the wire) and upcasts on arrival.  Unlike
    the dense path's gather-then-sum, the reduce-scatter accumulates AT
    the wire dtype across devices — one quantization per partial plus a
    narrow-dtype add chain of depth log(D), the documented cost of
    halving the scatter path's wire bytes."""
    ax = _require_flat_mesh(mesh, "update_sharding='scatter'")
    w = jnp.asarray(w_matrix, dtype=jnp.float32)

    def per_device(w_cols, x):
        # w_cols: [n, L] — this device's lanes' columns of W;
        # x: [L, Fb] local lane slab.
        part = jnp.tensordot(w_cols, x.astype(jnp.float32),
                             axes=[[1], [0]],
                             precision=MIX_PRECISION)  # [n, Fb] partial
        if comm_dtype is not None:
            part = part.astype(comm_dtype)
        own = jax.lax.psum_scatter(part, ax, scatter_dimension=0,
                                   tiled=True)         # [L, Fb] mine
        return own.astype(x.dtype)

    fn = jax.shard_map(per_device, mesh=mesh,
                       in_specs=(P(None, ax), P(ax)),
                       out_specs=P(ax))
    with jax.named_scope("dopt_mix"):
        return [fn(w, b) for b in buckets]


def mix_update_scatter(stacked, arg, mesh: Mesh, spec: UpdateShardSpec,
                       shift_ids=None, comm_dtype=None):
    """The engine-facing scatter-mode consensus step: flatten the
    stacked tree into the spec's buckets, mix every bucket (dense
    reduce-scatter, or the sharded circulant contraction when the
    schedule decomposed into shifts — ``mix_shifts`` over flat buckets
    ships the SAME lane unions per rotation, just as size-bounded flat
    chunks instead of per-leaf payloads), and restore the tree.

    ``comm_dtype`` narrows the wire hop of whichever collective runs:
    the ppermute payloads on the shift path, the reduce-scatter
    partials on the dense path — the same one-knob wire compression the
    plain (unsharded) collectives expose."""
    buckets = stacked_to_buckets(stacked, spec)
    if shift_ids is not None:
        with jax.named_scope("dopt_mix"):
            mixed = mix_shifts(buckets, shift_ids, arg, mesh, comm_dtype)
    else:
        mixed = mix_dense_scatter(buckets, arg, mesh, comm_dtype)
    return buckets_to_stacked(mixed, spec)


def masked_average_scatter(stacked, mask, mesh: Mesh,
                           spec: UpdateShardSpec, denom=None,
                           comm_dtype=None):
    """Sharded-update formulation of ``masked_average`` (Xu et al.,
    arXiv:2004.13336): each device reduces its local lanes' masked
    partial sum per bucket, ``psum_scatter`` leaves each device owning
    a 1/D shard of the flat sum, the aggregation update (the divide)
    runs on that shard only, and ONE tiled all-gather re-forms the
    replicated θ — instead of every device redundantly computing the
    full |θ| average.  Returns the unstacked θ tree.

    ``denom`` (optional traced scalar) overrides the divisor: the
    hierarchical-aggregation path (``dopt.population``) accumulates
    per-lane weighted sums over multiple cohort WAVES and then needs
    Σ_lanes acc / total_cohort_weight — the lane mask alone no longer
    knows the true weight, so the caller supplies it (already guarded
    against zero).

    ``comm_dtype`` narrows the reduce hop (the psum_scatter of the
    masked partials) — accumulation happens AT the wire dtype across
    devices, mirroring ``mix_dense_scatter``; the 1/D update divide and
    the re-forming all-gather stay at the leaf dtype so θ itself is
    never narrowed twice."""
    ax = _require_flat_mesh(mesh, "update_sharding='scatter'")
    m = jnp.asarray(mask, dtype=jnp.float32)
    denom = (jnp.maximum(m.sum(), 1.0) if denom is None
             else jnp.asarray(denom, jnp.float32))
    buckets = stacked_to_buckets(stacked, spec)

    def per_device(mask_l, x):
        mm = mask_l.reshape((-1,) + (1,) * (x.ndim - 1))
        part = (x.astype(jnp.float32) * mm).sum(axis=0)     # [Fb] partial
        if comm_dtype is not None:
            part = part.astype(comm_dtype)
        shard = jax.lax.psum_scatter(part, ax, scatter_dimension=0,
                                     tiled=True)            # [Fb/D] mine
        with jax.named_scope("dopt_update"):
            upd = (shard.astype(jnp.float32) / denom).astype(x.dtype)
        return jax.lax.all_gather(upd, ax, axis=0, tiled=True)

    # all_gather of identical shards IS replicated but cannot be
    # statically proven so — skip the varying-axes check, mirroring
    # _masked_average_compressed.
    fn = jax.shard_map(per_device, mesh=mesh,
                       in_specs=(P(ax), P(ax)), out_specs=P(),
                       check_vma=False)
    with jax.named_scope("dopt_mix"):
        out = [fn(m, b) for b in buckets]
    return buckets_to_tree(out, spec)


# ---------------------------------------------------------------------
# Per-bucket wire codecs (CommConfig): the communication substrate
# ---------------------------------------------------------------------
# Every compressed mode now speaks the SAME flat-bucket representation
# the scatter path already uses: a bucket's [L, Fb] lane slab is
# encoded (dopt.ops.compression.qint_encode — per-chunk-scaled
# stochastic int8, or nibble-packed int4), the PACKED payload is what
# crosses the wire, each device decodes the gathered fleet payloads
# locally and contracts its own mixing-matrix rows.  A reduce-scatter
# cannot sum packed payloads, so the codec path is a compressed
# all-gather formulation: wire bytes drop from the dense path's
# 4·|bucket| f32 to |bucket|·bits/8 + the f32 scale sidecar (~4x at
# int8, ~7.9x at int4), at the cost of materialising the decoded
# [n, Fb] slab per bucket — the classic compression/memory trade the
# bandwidth schedule only takes on buckets worth compressing.
#
# Error feedback (DeepSqueeze/CHOCO-style): v = x + e is encoded, the
# residual e' = v − decode(encode(v)) stays local and re-enters next
# round, so the quantization error is fed back instead of compounding
# — the convergence-preserving half of the contract.  The residual is
# carried scan state in the engines and checkpointed ("comm_residual").

_WIRE_KINDS = ("raw", "bf16", "f16", "q8", "q4")


@dataclasses.dataclass(frozen=True)
class BucketCodecPlan:
    """Static per-bucket wire schedule for an ``UpdateShardSpec``.

    ``kinds[i]`` names bucket i's wire format: ``raw`` (leaf dtype,
    the exact scatter path), ``bf16``/``f16`` (dtype narrowing),
    ``q8``/``q4`` (packed integer codec with error feedback).  Built
    once at trainer construction by ``make_codec_plan`` — the schedule
    is compiled structure, never data."""

    kinds: tuple[str, ...]
    chunk: int
    dense_bytes: int   # per-lane f32 wire bytes of the whole tree/round
    wire_bytes: int    # per-lane scheduled wire bytes of the same

    @property
    def any_codec(self) -> bool:
        return any(k in ("q8", "q4") for k in self.kinds)

    @property
    def compression(self) -> float:
        return self.dense_bytes / max(self.wire_bytes, 1)


def _bucket_wire_bytes(width: int, kind: str, chunk: int) -> int:
    from dopt.ops.compression import qint_wire_bytes

    if kind == "raw":
        return width * 4
    if kind in ("bf16", "f16"):
        return width * 2
    return qint_wire_bytes(width, chunk=chunk,
                           bits=8 if kind == "q8" else 4)


def make_codec_plan(spec: UpdateShardSpec, *, codec: str = "none",
                    wire_dtype=None, byte_budget: int = 0,
                    min_codec_bytes: int = 4096,
                    chunk: int = 1024) -> BucketCodecPlan:
    """Map a byte budget onto per-bucket wire formats.

    Base format: ``wire_dtype`` narrowing (or ``raw``).  With a codec
    armed and no budget, every bucket whose per-lane f32 payload is at
    least ``min_codec_bytes`` gets the codec — small norm/bias buckets
    stay exact, the big conv/matmul slabs compress.  With
    ``byte_budget`` > 0 (per lane per round, e.g. from
    ``link_byte_budget``) buckets are escalated LARGEST FIRST —
    base → q8 → q4 — until the total fits the budget or every eligible
    bucket is at q4; large buckets therefore always compress at least
    as hard as small ones, and the schedule degrades gracefully when
    the budget is unreachable."""
    if codec not in ("none", "qsgd"):
        raise ValueError(f"unknown comm codec {codec!r}; one of none|qsgd")
    base = {None: "raw", "bfloat16": "bf16", "float16": "f16"}.get(
        str(wire_dtype) if wire_dtype is not None else None)
    if base is None:
        raise ValueError(
            f"unknown comm wire_dtype {wire_dtype!r}; one of "
            "bfloat16|float16 (or None for the leaf dtype)")
    widths = [b - a for a, b in zip(spec.bounds, spec.bounds[1:])]
    dense = sum(w * 4 for w in widths)
    kinds = [base] * len(widths)
    eligible = [i for i, w in enumerate(widths)
                if codec != "none" and w * 4 >= min_codec_bytes]
    by_size = sorted(eligible, key=lambda i: -widths[i])
    if codec != "none" and byte_budget <= 0:
        for i in eligible:
            kinds[i] = "q8"
    elif codec != "none":
        def total():
            return sum(_bucket_wire_bytes(w, k, chunk)
                       for w, k in zip(widths, kinds))

        for tier in ("q8", "q4"):
            for i in by_size:
                if total() <= byte_budget:
                    break
                kinds[i] = tier
    wire = sum(_bucket_wire_bytes(w, k, chunk)
               for w, k in zip(widths, kinds))
    return BucketCodecPlan(kinds=tuple(kinds), chunk=int(chunk),
                           dense_bytes=int(dense), wire_bytes=int(wire))


def link_byte_budget(dense_bytes: int, *, msg_drop: float = 0.0,
                     msg_delay: float = 0.0,
                     msg_delay_max: int = 0) -> int:
    """Per-link per-round byte budget implied by a lossy-link model
    (``FaultConfig.msg_drop``/``msg_delay``/``msg_delay_max``): a link
    that loses fraction p of its messages and delays fraction q of the
    rest by up to D rounds delivers useful bytes at goodput factor
    (1 − p) / (1 + q·D) of its raw rate — so a round's exchange only
    fits the round if the payload shrinks by that factor.  This is the
    bandwidth-aware schedule's input: the model that MOTIVATES
    compression prices it."""
    p = min(max(float(msg_drop), 0.0), 0.99)
    q = min(max(float(msg_delay), 0.0), 1.0)
    d = max(int(msg_delay_max), 0)
    factor = (1.0 - p) / (1.0 + q * d)
    return max(int(dense_bytes * factor), 1)


def _codec_mix_bucket(w_rows, x, e, lane0, kind: str, chunk: int, key,
                      ax: str | None):
    """One bucket's compressed-gather mix on ONE device (or the dense
    reference when ``ax`` is None): encode v = x + e per local lane,
    gather the packed payloads, decode the fleet slab, contract this
    device's mixing rows.  Returns (mixed [L, Fb], residual' [L, Fb]).

    The encode keys fold the GLOBAL lane id, so the bits for lane i are
    identical whether i is encoded here (shard_map) or in the reference
    — the scatter-vs-dense parity contract for stochastic codecs."""
    from dopt.ops.compression import qint_decode, qint_encode

    l, fb = x.shape
    bits = 8 if kind == "q8" else 4
    lane_ids = lane0 + jnp.arange(l)
    v = x.astype(jnp.float32) + e
    payload, scale = qint_encode(v, lane_ids, key, chunk=chunk, bits=bits)
    vq = qint_decode(payload, scale, fb, chunk=chunk, bits=bits)
    new_e = v - vq
    if ax is not None:
        payload = jax.lax.all_gather(payload, ax, axis=0, tiled=True)
        scale = jax.lax.all_gather(scale, ax, axis=0, tiled=True)
        vg = qint_decode(payload, scale, fb, chunk=chunk, bits=bits)
    else:
        vg = vq
    y = jnp.tensordot(w_rows, vg, axes=[[1], [0]],
                      precision=MIX_PRECISION)            # [L, Fb]
    return y.astype(x.dtype), new_e


def mix_codec_gather(buckets, residuals, w_matrix, mesh: Mesh,
                     plan: BucketCodecPlan, key):
    """Compressed consensus over flat buckets: per-bucket encode →
    all-gather of the PACKED payload (+ f32 scale sidecar) →
    local decode → this device's mixing rows contracted against the
    decoded fleet slab.  ``raw``/narrowed buckets keep the exact
    reduce-scatter path (``mix_dense_scatter``) — the codec only
    replaces the wire where the schedule says it pays.

    ``key`` is the round-folded base key; bucket i folds its index on
    top, and the per-lane fold happens inside the encode — draws are a
    pure function of (round, bucket, global lane).  Returns
    ``(mixed_buckets, new_residuals)`` with residuals of codec buckets
    updated (v − decode(encode(v))) and others passed through."""
    ax = _require_flat_mesh(mesh, "comm codec")
    w = jnp.asarray(w_matrix, dtype=jnp.float32)
    n = w.shape[0]
    lanes = n // mesh.size
    mixed, new_res = [], []
    with jax.named_scope("dopt_mix"):
        for i, (b, e, kind) in enumerate(
                zip(buckets, residuals, plan.kinds)):
            if kind in ("q8", "q4"):
                bkey = jax.random.fold_in(key, i)

                def per_device(w_rows, x, er, _kind=kind, _bkey=bkey):
                    lane0 = jax.lax.axis_index(ax) * lanes
                    return _codec_mix_bucket(w_rows, x, er, lane0, _kind,
                                             plan.chunk, _bkey, ax)

                fn = jax.shard_map(
                    per_device, mesh=mesh,
                    in_specs=(P(ax, None), P(ax), P(ax)),
                    out_specs=(P(ax), P(ax)))
                y, e2 = fn(w, b, e)
                mixed.append(y)
                new_res.append(e2)
            else:
                cd = {"raw": None, "bf16": jnp.bfloat16,
                      "f16": jnp.float16}[kind]
                mixed.append(mix_dense_scatter([b], w, mesh, cd)[0])
                new_res.append(e)
    return mixed, new_res


def mix_codec_reference(buckets, residuals, w_matrix,
                        plan: BucketCodecPlan, key):
    """Dense (no-mesh) reference of ``mix_codec_gather`` — the global
    [W, Fb] view with lane ids 0..W−1, drawing the SAME per-lane bits.
    The parity oracle for tests: sharded and reference paths agree to
    f32 tolerance (bit-equal encodes; the contraction differs only by
    gather layout)."""
    w = jnp.asarray(w_matrix, dtype=jnp.float32)
    mixed, new_res = [], []
    for i, (b, e, kind) in enumerate(zip(buckets, residuals, plan.kinds)):
        if kind in ("q8", "q4"):
            y, e2 = _codec_mix_bucket(w, b, e, 0, kind, plan.chunk,
                                      jax.random.fold_in(key, i), None)
            mixed.append(y)
            new_res.append(e2)
        else:
            cd = {"raw": None, "bf16": jnp.bfloat16,
                  "f16": jnp.float16}[kind]
            x = b if cd is None else b.astype(cd).astype(jnp.float32)
            y = jnp.tensordot(w, x.astype(jnp.float32), axes=[[1], [0]],
                              precision=MIX_PRECISION)
            mixed.append(y.astype(b.dtype))
            new_res.append(e)
    return mixed, new_res


# ---------------------------------------------------------------------
# Compiled-HLO collective byte accounting
# ---------------------------------------------------------------------

_HLO_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
              "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8,
              "u64": 8, "f64": 8, "c64": 8, "c128": 16}

_HLO_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter",
                    "collective-permute", "all-to-all")

_SHAPE_RE = re.compile(r"\b([a-z]+[0-9]+|pred)\[([0-9,]*)\]")


def _shape_bytes(shape_text: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_text):
        if dtype not in _HLO_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _HLO_BYTES[dtype]
    return total


def _shape_bytes_by_dtype(shape_text: str) -> dict[str, int]:
    by: dict[str, int] = {}
    for dtype, dims in _SHAPE_RE.findall(shape_text):
        if dtype not in _HLO_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        by[dtype] = by.get(dtype, 0) + n * _HLO_BYTES[dtype]
    return by


def hlo_collective_bytes(hlo_text: str) -> dict:
    """Count the result-buffer bytes of every cross-device collective in
    a compiled HLO dump (``jit(fn).lower(...).compile().as_text()``):
    ``{op_kind: bytes, ..., "total": bytes, "by_dtype": {dtype: bytes},
    "by_op_dtype": {op_kind: {dtype: bytes}}}``.

    This is the measured basis for comm-volume claims — e.g. the folded
    shift path's "2 lane-shards per device vs the dense all_gather's
    n − L" (``tests/test_collectives.py`` pins it against the compiled
    programs, not the docstring).  Result-buffer bytes upper-bound wire
    bytes proportionally (an all-gather's result includes the local
    shard), which cancels in path-vs-path comparisons.  Async pairs
    (``*-start``/``*-done``) are counted once, at the start op.

    The per-dtype attribution is what makes COMPRESSED wires auditable:
    a ``comm_dtype='bfloat16'`` run shows its gather bytes under
    ``bf16``, a packed int8/int4 codec run under ``s8``/``u8`` with the
    f32 scale sidecars accounted separately — so "4x fewer bytes" is a
    statement about the compiled program, not the docstring."""
    out: dict = {k: 0 for k in _HLO_COLLECTIVES}
    by_dtype: dict[str, int] = {}
    by_op: dict[str, dict[str, int]] = {k: {} for k in _HLO_COLLECTIVES}
    for line in hlo_text.splitlines():
        if "=" not in line:
            continue
        rhs = line.partition("=")[2].strip()
        for kind in _HLO_COLLECTIVES:
            m = re.search(rf"(^|\s){re.escape(kind)}(-start)?\(", rhs)
            if m:
                per = _shape_bytes_by_dtype(rhs[:m.start()])
                for dt, b in per.items():
                    out[kind] += b
                    by_dtype[dt] = by_dtype.get(dt, 0) + b
                    by_op[kind][dt] = by_op[kind].get(dt, 0) + b
                break
    out["total"] = sum(out[k] for k in _HLO_COLLECTIVES)
    out["by_dtype"] = by_dtype
    out["by_op_dtype"] = {k: v for k, v in by_op.items() if v}
    return out


def broadcast_to_workers(tree, num_workers: int):
    """theta → stacked [W, ...] (the server handing every client a copy
    of the global model, servers.py:63 — here a free broadcast)."""
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (num_workers,) + x.shape), tree
    )


def mix_power(stacked, w_matrix, eps: int = 1, mesh: Mesh | None = None,
              comm_dtype=None):
    """eps consensus sweeps (FedLCon, simulators.py:182-212 — with the
    stale-accumulation bug fixed: each sweep reads the previous sweep's
    output).  eps=1 is plain consensus; jit at the caller."""
    if eps == 1:
        return mix_dense(stacked, w_matrix, mesh, comm_dtype)

    def body(x, _):
        return mix_dense(x, w_matrix, mesh, comm_dtype), None

    out, _ = jax.lax.scan(body, stacked, None, length=eps)
    return out
