"""The held experts of a mixture-of-experts layer as a DROPLESS GROUPED
matmul: only the routed slots are multiplied, sorted by expert, whatever
the routing (``dopt.models.decoder._experts`` calls it; the dense
dispatch it took the place of is the definition it is held to, in
``tests/test_decoder.py``).

Arrangement.  A SLOT is one (token, held expert) pair the router chose.
``slot_layout`` puts the slots of a whole fleet in one order: by
(worker, expert) GROUP, a group padded to whole TILES of ``TILE`` slots
and given at least one (so every group's weight gradient is written),
within a group by token.  Where a slot sits is data; how many slots
there can be is not (every token choosing every held expert: tokens x
min(k, held)), so the slot arrays have the worst case's length and what
runs over them visits only the tiles in use: the kernels' grid is as
long as those (a dynamic grid), not as the arrays.

Two bodies, one arrangement (``path`` says which, from shapes alone):

* ``"grouped-fused"``: three Pallas TPU kernels, ONE call each a fleet.
  ``..._fwd`` copies a tile's token rows from HBM by their row numbers
  (one DMA a float32 row, rounded to the compute dtype in VMEM),
  multiplies them with the group's gate and up matrices a slice of the
  expert's width at a time (the float32 leaves are rounded in VMEM too:
  a cast outside would be a pass over 100 MB of its own), writes the two
  products (what a layer's ``jax.checkpoint`` keeps), and adds
  ``act(g) * u @ down`` times the slot's combine weight back onto the
  tokens' rows of the float32 output (rows read, added to and written
  back by DMA: a token is in a tile at most once, and tiles run one
  after another).  ``..._dx`` does the same walk for the gradients of
  the input, of the combine weights and of the two products; ``..._dw``
  sums each group's three weight gradients in VMEM over the group's
  tiles.  No ``[slots, hidden]`` array exists in HBM.
* ``"grouped"``: the same slots, gathered, multiplied against their
  group's matrices and added back in ``jax.numpy`` (toy widths on the
  CPU, the rehearsals), differentiated by jax.

The engines ``vmap`` over workers and the model over rows.  A kernel
with prefetched scalars under ``vmap`` becomes a sequential loop over
the batch, so the fused body is a primitive with a batching rule of its
own over arrays that carry their worker axis (``_fleet``): a batch of
workers is folded into the GROUPS, a batch of rows (the experts' leaves
unbatched) into the tokens.

Matmul inputs in the compute dtype with float32 accumulation, pinned to
``DEFAULT`` precision (Mosaic refuses bfloat16 operands at the
``highest`` a parity check sets around the program, PERF.md, PR 28); a
slot's output is weighted and added to its token in float32; the
experts' gradients are summed in float32, leave the kernel in the
compute dtype, as the cotangent of a leaf cast to it does, and come back
in the parameters' dtype.  ``act`` (static: SwiGLU's ``silu``, ReGLU's
``relu``) is the gate's activation in both bodies and all three
kernels.  The kernels' names spell out the scope they stand in,
``dopt_moe``: a trace's readers find a scope as a substring of an
instruction's name and name stack, and a custom call may carry no stack.
Compiled on ``tpu``, interpreted on ``cpu`` (``dopt.ops.pallas_interpret``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.extend import core as jex_core
from jax.interpreters import batching, mlir


KERNEL_NAMES = {kind: f"dopt_moe_experts_{kind}"
                for kind in ("fwd", "dx", "dw")}
# Slots a tile.  A held expert of the benchmark's cells sees 128 (laguna)
# or 512 (keye) slots a step and row; the row copies and the weights'
# bytes, not the products, bound the kernels (PERF.md, PR 34).
TILE = 128
_LANES = 128
# The slice of an expert's width a grid step of ``_fwd`` and ``_dx``
# holds: its three float32 matrices, held twice by the pipeline, must fit
# the 16 MiB of VMEM a kernel gets beside a tile's rows.
WIDTH_SLICE = _LANES
_NT = (((1,), (1,)), ((), ()))     # a @ b.T
# The experts' gate activations: SwiGLU's ``silu`` and ReGLU's ``relu``
# (whose derivative is the step ``g > 0``, 0 at 0, as ``jax.nn.relu``'s).
ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def path(hidden: int, width: int) -> str:
    """Which body the held experts run: ``"grouped-fused"``, the Pallas
    kernels, wherever their shape limits allow it -- a token's float32 row
    whole lanes, so that as [hidden / 128, 128] it is a run of (8, 128)
    tiles that can be copied alone (the last one padded where hidden /
    128 is no multiple of 8: SmallThinker's 2,560 is 20 rows of lanes),
    and the expert's width whole lanes -- else ``"grouped"``, the same
    sorted slots in ``jax.numpy``.  Nothing else decides it: no option, no
    platform (the kernels are interpreted on the CPU).  No shape keeps the
    dense dispatch: it is the tests' definition only."""
    fits = hidden % _LANES == 0 and width % _LANES == 0
    return "grouped-fused" if fits else "grouped"


def _glu(act, g, u):
    """``act(g) * u`` in float32, as the kernels compute it."""
    if act == "silu":
        return g * jax.nn.sigmoid(g) * u
    return jnp.maximum(g, 0.0) * u


def _interpret() -> bool:
    from dopt import ops

    return ops.pallas_interpret()


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    return jax.lax.dot_general(a, b, dims, precision=jax.lax.Precision.DEFAULT,
                               preferred_element_type=jnp.float32)


# ------------------------------------------------------------------- layout

def slot_capacity(tokens: int, k: int, held: int) -> int:
    """Tiles a worker's slot arrays hold: the worst case, every token
    choosing ``min(k, held)`` held experts, each group's last tile
    partly filled or empty."""
    return -(-tokens * min(k, held) // TILE) + held


def running_count(flags):
    """[..., N] bool -> [..., N] float32, how many of a row's flags are
    set up to and with each position: exact, by two small matmuls over
    chunks of 128 (counts within a chunk, chunks before it) where a
    running sum along 8,192 positions would be a long serial pass."""
    *lead, n = flags.shape
    chunk = 128
    pad = -n % chunk
    x = jnp.pad(flags, [(0, 0)] * len(lead) + [(0, pad)])
    x = x.reshape(*lead, -1, chunk).astype(jnp.bfloat16)    # 0 / 1: exact
    upto = jnp.triu(jnp.ones((chunk, chunk), jnp.bfloat16))
    within = jnp.einsum("...cj,ji->...ci", x, upto,
                        preferred_element_type=jnp.float32)
    chunks = x.shape[-2]
    before = jnp.triu(jnp.ones((chunks, chunks), jnp.float32), 1)
    offset = jnp.einsum("...c,cd->...d", within[..., -1], before,
                        precision=jax.lax.Precision.HIGHEST)
    return (within + offset[..., None]).reshape(*lead, -1)[..., :n]


def slot_layout(hit, weight, *, k: int):
    """Where each slot of the fleet sits.  ``hit`` [W, N, E] bool (token
    n of worker w was routed to held expert e), ``weight`` [W, N, E]
    float32 (its combine weight there) ->

    * ``pos`` [W, N, E] int32: the slot of (w, n, e), past the arrays'
      end where not hit (``slot_values`` reads a slot array back by it);
    * ``token`` int32 and ``scale`` float32 [tiles, 1, TILE]: a slot's
      row of the fleet's [W * N] tokens and its combine weight (0 for
      padding);
    * ``group`` and ``rows`` [tiles] int32: a tile's (worker, expert)
      and how many of its slots are real (its first ``rows``);
    * ``used`` [1] int32: the tiles in use, at least one a group.

    The slot arrays hold P = W * slot_capacity * TILE slots, the worst
    case.  The two scatters of W * N * E candidates (6 ns each on this
    chip) are most of what the dispatch costs outside the kernels; a
    chunk of tokens placed as ONE window of slots was measured and is
    slower (PERF.md, PR 34)."""
    w, n, e = hit.shape
    tiles = w * slot_capacity(n, k, e)
    p = tiles * TILE
    upto = running_count(jnp.moveaxis(hit, 2, 1))                # [W, E, N]
    count = upto[..., -1].astype(jnp.int32).reshape(-1)         # [G]
    rank = jnp.moveaxis(upto, 1, 2).astype(jnp.int32) - 1       # where hit
    need = jnp.maximum(1, -(-count // TILE))
    end = jnp.cumsum(need)
    first = end - need
    # (every candidate its own place: the ones not hit past the end, apart)
    pos = jnp.where(hit, first.reshape(w, 1, e) * TILE + rank,
                    p + jnp.arange(w * n * e).reshape(w, n, e))
    row = jnp.broadcast_to((jnp.arange(w)[:, None] * n
                            + jnp.arange(n)[None, :])[..., None], pos.shape)

    def slots(values, dtype):
        return jnp.zeros(p, dtype).at[pos.reshape(-1)].set(
            values.reshape(-1), mode="drop", unique_indices=True
        ).reshape(tiles, 1, TILE)

    at = jnp.arange(tiles)
    group = jnp.minimum(jnp.sum(at[:, None] >= end[None, :], axis=1),
                        w * e - 1).astype(jnp.int32)
    rows = jnp.clip(count[group] - (at - first[group]) * TILE, 0, TILE)
    rows = jnp.where(at < end[-1], rows, 0).astype(jnp.int32)
    return (pos, slots(row, jnp.int32), slots(weight, jnp.float32), group,
            rows, end[-1:].astype(jnp.int32))


def slot_values(slots, pos):
    """[W, N, E]: the entry of the slot array ``slots`` (any shape, P
    entries) at each (worker, token, expert) that hit, 0 elsewhere."""
    slots = jnp.pad(slots.reshape(-1), (0, 1))
    return slots[jnp.minimum(pos, slots.size - 1)]


# ------------------------------------------------------------------ kernels

_BATCH = 8      # row copies started (and waited for) without a branch


def _fetch_rows(hbm, buf, sem, tok_ref, n):
    """``buf``'s first ``n`` rows (and up to ``_BATCH - 1`` more, which
    the caller ignores) = the tile's token rows of ``hbm``: one DMA a
    row, ``_BATCH`` rows an iteration of the loop, one wait a batch (a
    padding slot's token is row 0: read, never written)."""
    def start(c, carry):
        for r in range(_BATCH):
            at = c * _BATCH + r
            pltpu.make_async_copy(hbm.at[tok_ref[0, at]], buf.at[at],
                                  sem).start()
        return carry

    def wait(c, carry):
        pltpu.make_async_copy(hbm.at[pl.ds(0, _BATCH)],
                              buf.at[pl.ds(c * _BATCH, _BATCH)], sem).wait()
        return carry

    batches = (n + (_BATCH - 1)) // _BATCH
    jax.lax.fori_loop(0, batches, start, None)
    jax.lax.fori_loop(0, batches, wait, None)


def _store_rows(buf, hbm, sem, tok_ref, n):
    """The tile's token rows of ``hbm`` = ``buf``'s first ``n`` rows,
    exactly ``n`` copies: whole batches, then the rest one by one."""
    def copy(at):
        return pltpu.make_async_copy(buf.at[at], hbm.at[tok_ref[0, at]], sem)

    def start(c, carry):
        for r in range(_BATCH):
            copy(c * _BATCH + r).start()
        return carry

    def wait(c, carry):
        pltpu.make_async_copy(buf.at[pl.ds(c * _BATCH, _BATCH)],
                              hbm.at[pl.ds(0, _BATCH)], sem).wait()
        return carry

    def start_one(at, carry):
        copy(at).start()
        return carry

    def wait_one(at, carry):
        copy(0).wait()
        return carry

    whole = n // _BATCH
    jax.lax.fori_loop(0, whole, start, None)
    jax.lax.fori_loop(whole * _BATCH, n, start_one, None)
    jax.lax.fori_loop(0, whole, wait, None)
    jax.lax.fori_loop(whole * _BATCH, n, wait_one, None)


def _gather(hbm, buf, sem, tok_ref, n, out_ref):
    """``out_ref`` [tile, d] = the tile's token rows of ``hbm`` [M, d /
    128, 128] in ``out_ref``'s dtype, its first ``n`` rows; the rest 0
    (whatever the buffer held: ``where``, not a product).  A row of a
    [M, d] array is not contiguous under the chip's (8, 128) tiling and
    cannot be copied alone; as [d / 128, 128] it is whole tiles, and the
    lanes are put side by side again here, a slice at a time."""
    _fetch_rows(hbm, buf, sem, tok_ref, n)
    real = jax.lax.broadcasted_iota(jnp.int32, (buf.shape[0], _LANES), 0) < n
    zeros = jnp.zeros((buf.shape[0], _LANES), buf.dtype)
    for a in range(buf.shape[1]):
        out_ref[:, a * _LANES:(a + 1) * _LANES] = jax.lax.select(
            real, buf[:, a, :], zeros).astype(out_ref.dtype)


def _add_back(acc, hbm, buf, sem, tok_ref, n):
    """``hbm``'s token rows of the tile += ``acc``'s [tile, d] first ``n``
    rows."""
    _fetch_rows(hbm, buf, sem, tok_ref, n)
    for a in range(buf.shape[1]):
        buf[:, a, :] += acc[:, a * _LANES:(a + 1) * _LANES]
    _store_rows(buf, hbm, sem, tok_ref, n)


def _eye(tile):
    return (jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 0)
            == jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 1))


def _column(row):
    """[1, tile] -> [tile, 1], exactly (one term a sum)."""
    return jnp.sum(jnp.where(_eye(row.shape[1]), row, 0.0), axis=1,
                   keepdims=True)


def _row(column):
    """[tile, 1] -> [1, tile], exactly."""
    return jnp.sum(jnp.where(_eye(column.shape[0]), column, 0.0), axis=0,
                   keepdims=True)


def _fwd_kernel(group_ref, rows_ref, tok_ref, x_hbm, wg_ref, wu_ref, wd_ref,
                scale_ref, init_hbm, g_ref, u_ref, out_hbm, xbuf, xin, acc,
                sem, *, act):
    del group_ref, init_hbm           # (the index maps'; aliased to out_hbm)
    i, j = pl.program_id(0), pl.program_id(1)
    n = rows_ref[i]

    @pl.when(j == 0)
    def _():
        _gather(x_hbm, xbuf, sem, tok_ref, n, xin)
        acc[...] = jnp.zeros_like(acc)

    x = xin[...]
    g = _dot(x, wg_ref[...].astype(x.dtype)).astype(g_ref.dtype)
    u = _dot(x, wu_ref[...].astype(x.dtype)).astype(u_ref.dtype)
    g_ref[...] = g
    u_ref[...] = u
    g, u = g.astype(jnp.float32), u.astype(jnp.float32)
    mid = _glu(act, g, u).astype(x.dtype)
    acc[...] += _dot(mid, wd_ref[...].astype(x.dtype))

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        _add_back(acc[...] * _column(scale_ref[...]), out_hbm, xbuf, sem,
                  tok_ref, n)


def _dx_kernel(group_ref, rows_ref, tok_ref, dout_hbm, g_ref, u_ref, wg_ref,
               wu_ref, wd_ref, scale_ref, zero_hbm, dg_ref, du_ref,
               dscale_ref, dx_hbm, dbuf, din, acc, sem, *, act):
    del group_ref, zero_hbm
    i, j = pl.program_id(0), pl.program_id(1)
    n = rows_ref[i]

    @pl.when(j == 0)
    def _():
        _gather(dout_hbm, dbuf, sem, tok_ref, n, din)
        acc[...] = jnp.zeros_like(acc)
        dscale_ref[...] = jnp.zeros_like(dscale_ref)

    g, u = g_ref[...].astype(jnp.float32), u_ref[...].astype(jnp.float32)
    if act == "silu":
        gate = jax.nn.sigmoid(g)
        mid = g * gate * u
    else:
        mid = jnp.maximum(g, 0.0) * u
    mid = mid.astype(din.dtype).astype(jnp.float32)
    dt = din.dtype
    dmid = _dot(din[...], wd_ref[...].astype(dt), _NT)   # [tile, slice]
    dscale_ref[...] += _row(jnp.sum(dmid * mid, axis=1, keepdims=True))
    dmid = dmid * _column(scale_ref[...])
    if act == "silu":
        dg = (dmid * u * gate * (1.0 + g * (1.0 - gate))).astype(dg_ref.dtype)
        du = (dmid * g * gate).astype(du_ref.dtype)
    else:
        dg = jnp.where(g > 0.0, dmid * u, 0.0).astype(dg_ref.dtype)
        du = (dmid * jnp.maximum(g, 0.0)).astype(du_ref.dtype)
    dg_ref[...] = dg
    du_ref[...] = du
    acc[...] += (_dot(dg, wg_ref[...].astype(dt), _NT)
                 + _dot(du, wu_ref[...].astype(dt), _NT))

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        _add_back(acc[...], dx_hbm, dbuf, sem, tok_ref, n)


def _dw_kernel(group_ref, rows_ref, tok_ref, x_hbm, dout_hbm, g_ref, u_ref,
               dg_ref, du_ref, scale_ref, dwg_ref, dwu_ref, dwd_ref, buf,
               xin, din, dwg, dwu, dwd, sem, *, act):
    i = pl.program_id(0)
    n = rows_ref[i]
    last = group_ref.shape[0] - 1
    mine = group_ref[i]
    opens = jnp.logical_or(i == 0, mine != group_ref[jnp.maximum(i - 1, 0)])
    closes = jnp.logical_or(i == pl.num_programs(0) - 1,
                            mine != group_ref[jnp.minimum(i + 1, last)])

    @pl.when(opens)
    def _():
        dwg[...] = jnp.zeros_like(dwg)
        dwu[...] = jnp.zeros_like(dwu)
        dwd[...] = jnp.zeros_like(dwd)

    @pl.when(n > 0)
    def _():
        dt = dg_ref.dtype
        _gather(x_hbm, buf, sem, tok_ref, n, xin)
        _gather(dout_hbm, buf, sem, tok_ref, n, din)
        xt = xin[...].T                                      # [hidden, tile]
        dwg[...] += _dot(xt, dg_ref[...])
        dwu[...] += _dot(xt, du_ref[...])
        g, u = g_ref[...].astype(jnp.float32), u_ref[...].astype(jnp.float32)
        mid = _glu(act, g, u).astype(dt).astype(jnp.float32)
        mid = (mid * _column(scale_ref[...])).astype(dt)
        dwd[...] += _dot(mid.T, din[...])                    # [width, hidden]

    @pl.when(closes)
    def _():
        dwg_ref[...] = dwg[...].astype(dwg_ref.dtype)
        dwu_ref[...] = dwu[...].astype(dwu_ref.dtype)
        dwd_ref[...] = dwd[...].astype(dwd_ref.dtype)


def _grid_spec(used, grid, in_specs, out_specs, scratch):
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=grid(used[0]), in_specs=in_specs,
        out_specs=out_specs, scratch_shapes=scratch)


_ANY = pl.BlockSpec(memory_space=pl.ANY)


def _leaves(d, s):
    """A grid step's slices of its group's gate, up and down matrices."""
    wide = pl.BlockSpec((None, d, s), lambda i, j, grp, *_: (grp[i], 0, j))
    return [wide, wide,
            pl.BlockSpec((None, s, d), lambda i, j, grp, *_: (grp[i], j, 0))]


def _tokens(index_map):
    """A tile's token rows, in SMEM (the DMAs' addresses are scalars): a
    block a grid step, because the whole array of a large fleet passes
    the 1 MiB of SMEM a kernel may hold."""
    return pl.BlockSpec((None, 1, TILE), index_map, memory_space=pltpu.SMEM)


@functools.partial(jax.jit, static_argnames=("dtype", "act", "interpret"))
def _forward(x, wg, wu, wd, init, token, scale, group, rows, used, *,
             dtype, act: str, interpret: bool):
    """x and init [M, d / 128, 128] float32, wg and wu [G, d, F], wd
    [G, F, d] in the parameters' dtype (the leaves are rounded to the
    compute ``dtype`` in VMEM: a cast outside would be a pass over them of
    its own) -> (init + the slots' weighted outputs, as init, the slots'
    gate and up products [P, F] in ``dtype``); ``act`` the gate's
    activation (``ACTIVATIONS``)."""
    d, f = wg.shape[1:]
    s, dt = WIDTH_SLICE, dtype
    g, u, out = pl.pallas_call(
        functools.partial(_fwd_kernel, act=act),
        grid_spec=_grid_spec(
            used, lambda t: (t, f // s),
            [_tokens(lambda i, j, *_: (i, 0, 0)), _ANY, *_leaves(d, s),
             pl.BlockSpec((None, 1, TILE), lambda i, j, *_: (i, 0, 0)),
             _ANY],
            [pl.BlockSpec((TILE, s), lambda i, j, *_: (i, j)),
             pl.BlockSpec((TILE, s), lambda i, j, *_: (i, j)),
             _ANY],
            [pltpu.VMEM((TILE, d // _LANES, _LANES), jnp.float32),
             pltpu.VMEM((TILE, d), dt), pltpu.VMEM((TILE, d), jnp.float32),
             pltpu.SemaphoreType.DMA(())]),
        out_shape=[jax.ShapeDtypeStruct((token.size, f), dt)] * 2
        + [jax.ShapeDtypeStruct(init.shape, init.dtype)],
        input_output_aliases={8: 2},   # init -> out
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name=KERNEL_NAMES["fwd"],
    )(group, rows, token, x, wg, wu, wd, scale, init)
    return out, g, u


@functools.partial(jax.jit, static_argnames=("act", "interpret"))
def _backward_dx(dout, g, u, wg, wu, wd, token, scale, group, rows, used, *,
                 act: str, interpret: bool):
    """-> (dx [M, d / 128, 128] float32, dscale [tiles, 1, tile], dg and
    du [P, F] in g's dtype)."""
    d, f = wg.shape[1:]
    s, dt = WIDTH_SLICE, g.dtype
    slots = pl.BlockSpec((TILE, s), lambda i, j, *_: (i, j))
    per_tile = pl.BlockSpec((None, 1, TILE), lambda i, j, *_: (i, 0, 0))
    dg, du, dscale, dx = pl.pallas_call(
        functools.partial(_dx_kernel, act=act),
        grid_spec=_grid_spec(
            used, lambda t: (t, f // s),
            [_tokens(lambda i, j, *_: (i, 0, 0)), _ANY, slots, slots,
             *_leaves(d, s), per_tile, _ANY],
            [slots, slots, per_tile, _ANY],
            [pltpu.VMEM((TILE, d // _LANES, _LANES), jnp.float32),
             pltpu.VMEM((TILE, d), dt), pltpu.VMEM((TILE, d), jnp.float32),
             pltpu.SemaphoreType.DMA(())]),
        out_shape=[jax.ShapeDtypeStruct(g.shape, dt)] * 2
        + [jax.ShapeDtypeStruct(scale.shape, jnp.float32),
           jax.ShapeDtypeStruct(dout.shape, jnp.float32)],
        input_output_aliases={10: 3},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name=KERNEL_NAMES["dx"],
    )(group, rows, token, dout, g, u, wg, wu, wd, scale,
      jnp.zeros(dout.shape, jnp.float32))
    return dx, dscale, dg, du


@functools.partial(jax.jit, static_argnames=("groups", "act", "interpret"))
def _backward_dw(x, dout, g, u, dg, du, token, scale, group, rows, used, *,
                 groups: int, act: str, interpret: bool):
    """-> (dwg and dwu [G, d, F], dwd [G, F, d]) in g's dtype, the compute
    dtype, as the cotangent of a leaf cast to it is (summed in float32)."""
    d, f = x.shape[1] * x.shape[2], g.shape[1]
    dt = g.dtype
    # The whole width a step: a tile's rows are copied once, not once a
    # slice (the copies, not the products, bound the kernel), so a group's
    # three gradients (float32 while they are summed, and the blocks they
    # leave through, held twice by the pipeline) pass the 16 MiB of VMEM a
    # kernel gets by default.
    vmem = 3 * d * f * (4 + 2 * dt.itemsize) + (16 << 20)
    slots = pl.BlockSpec((TILE, f), lambda i, *_: (i, 0))
    per_tile = lambda i, *_: (i, 0, 0)
    mine = lambda i, grp, *_: (grp[i], 0, 0)
    return pl.pallas_call(
        functools.partial(_dw_kernel, act=act),
        grid_spec=_grid_spec(
            used, lambda t: (t,),
            [_tokens(per_tile), _ANY, _ANY, slots, slots, slots, slots,
             pl.BlockSpec((None, 1, TILE), per_tile)],
            [pl.BlockSpec((None, d, f), mine), pl.BlockSpec((None, d, f), mine),
             pl.BlockSpec((None, f, d), mine)],
            [pltpu.VMEM((TILE, d // _LANES, _LANES), jnp.float32),
             pltpu.VMEM((TILE, d), dt), pltpu.VMEM((TILE, d), dt),
             pltpu.VMEM((d, f), jnp.float32), pltpu.VMEM((d, f), jnp.float32),
             pltpu.VMEM((f, d), jnp.float32), pltpu.SemaphoreType.DMA(())]),
        out_shape=[jax.ShapeDtypeStruct((groups, d, f), dt)] * 2
        + [jax.ShapeDtypeStruct((groups, f, d), dt)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=vmem),
        interpret=interpret,
        name=KERNEL_NAMES["dw"],
    )(group, rows, token, x, dout, g, u, dg, du, scale)


# ------------------------------------------------------------------ surface

def _fleet(name, fn, ins: str, outs: str, shapes):
    """``fn`` over arrays that carry their worker axis in front, as a
    primitive of its own; one letter an argument and a result: ``t``
    [W, N, ...] (a row of tokens a worker) or ``w`` [W, ...];
    ``shapes(*avals)`` its results' (shape, dtype).  Two reasons for a
    primitive.  Its batching rule keeps a ``vmap`` ONE call: a batch of
    workers (any ``w`` argument batched) becomes more workers, a batch of
    rows under one set of experts more tokens a worker, and then the
    ``w`` results (a worker's slot arrays, its weight gradients summed
    over the rows) are not batched (a ``custom_vjp``'s backward rule may
    hand an unbatched cotangent to an unbatched input: ``vmap`` sums over
    the batch only what IS batched).  And ``fn`` is traced when the
    primitive is LOWERED, at the shapes the program runs: the model calls
    it a row of one worker at a time, and a ``custom_vmap`` with the same
    rule traces the kernels at those shapes too and at every fold on the
    way (four of a round's seven traces of them are never lowered;
    PERF.md, PR 34, has the set-up times of both on the chip)."""
    call = jex_core.Primitive(name)
    call.multiple_results = True
    call.def_impl(fn)
    call.def_abstract_eval(lambda *avals: [
        jax.core.ShapedArray(shape, dtype) for shape, dtype in shapes(*avals)])
    mlir.register_lowering(call, mlir.lower_fun(fn, multiple_results=True))

    def rule(args, dims):
        size = next(a.shape[d] for a, d in zip(args, dims)
                    if d is not batching.not_mapped)

        def whole(a, d):
            return (jnp.broadcast_to(a, (size, *a.shape))
                    if d is batching.not_mapped else jnp.moveaxis(a, d, 0))

        if any(d is not batching.not_mapped and kind == "w"
               for d, kind in zip(dims, ins)):
            out = call.bind(*(_flat(whole(a, d)) for a, d in zip(args, dims)))
            return ([r.reshape(size, -1, *r.shape[1:]) for r in out],
                    [0] * len(outs))

        def rows(a, d):            # [B, W, N, ...] -> [W, B * N, ...]
            a = jnp.moveaxis(whole(a, d), 0, 1)
            return a.reshape(a.shape[0], -1, *a.shape[3:])

        out = call.bind(*(rows(a, d) if kind == "t" else a
                          for a, d, kind in zip(args, dims, ins)))
        return ([jnp.moveaxis(r.reshape(r.shape[0], size, -1, *r.shape[2:]),
                              1, 0) if kind == "t" else r
                 for r, kind in zip(out, outs)],
                [0 if kind == "t" else batching.not_mapped for kind in outs])

    batching.primitive_batchers[call] = rule
    return call.bind


def _rows_of(x):
    """[W, N, d] -> [W * N, d / 128, 128], a token a row of whole tiles."""
    return x.reshape(-1, x.shape[-1] // _LANES, _LANES)


def _flat(a):
    """[W, n, ...] -> [W * n, ...]: the fleet's groups, slots or tiles."""
    return a.reshape(-1, *a.shape[2:])


def zero_share(g, rows, worker, workers: int):
    """[workers] float32: each worker's share of the entries of
    ``relu(g)`` that are 0 over its real slots.  g [P, F] the gate
    products of ``tiles`` tiles of ``TILE`` slots, rows [tiles] how many
    of a tile's slots are real and worker [tiles] whose they are
    (``slot_layout``: a fleet's tiles in use lie one worker's after
    another's, packed, so the worker of a tile is read from its group)."""
    real = jnp.arange(TILE)[None, :] < rows[:, None]             # [tiles, T]
    zeros = jnp.sum((g.reshape(-1, TILE, g.shape[-1]) <= 0)
                    & real[..., None], axis=(1, 2))
    mine = worker[:, None] == jnp.arange(workers)                # [tiles, W]

    def by_worker(count):
        return jnp.sum(jnp.where(mine, count[:, None], 0),
                       axis=0).astype(jnp.float32)

    return by_worker(zeros) / jnp.maximum(
        by_worker(jnp.sum(real, axis=1)) * g.shape[-1], 1.0)


# (jitted: the four layers of a round lower ONE trace of it)
@functools.partial(jax.jit, static_argnames=("k", "dtype", "act"))
def _fwd_fleet(x, hit, weight, wg, wu, wd, init, *, k, dtype, act):
    """x and init [W, N, d] float32, hit and weight [W, N, E], the
    experts' leaves [W, E, ...] -> (init + the held experts' part, then
    what the backward pass takes beside the inputs: the gate and up
    products and the layout, every array [W, ...]; for ``relu``, last,
    each worker's ``zero_share``)."""
    w = hit.shape[0]
    with jax.named_scope("dopt_route"):
        pos, *layout = slot_layout(hit, weight, k=k)
    out, g, u = _forward(
        _rows_of(x), _flat(wg), _flat(wu), _flat(wd), _rows_of(init),
        *layout, dtype=dtype, act=act, interpret=_interpret())
    token, scale, group, rows, used = layout
    counted = ()
    if act == "relu":
        counted = (zero_share(g, rows, group // hit.shape[2], w),)
    return (out.reshape(x.shape), pos, *(
        a.reshape(w, -1, *a.shape[1:])
        for a in (g, u, token, scale, group, rows)),
        jnp.broadcast_to(used, (w, 1)), *counted)


def _fwd_shapes(x, hit, weight, wg, wu, wd, init, *, k, dtype, act):
    (w, n, e), tiles = hit.shape, slot_capacity(hit.shape[1], k, hit.shape[2])
    products = ((w, tiles * TILE, wg.shape[-1]), dtype)
    counted = [((w,), jnp.float32)] if act == "relu" else []
    return [(x.shape, x.dtype), ((w, n, e), jnp.int32), products, products,
            ((w, tiles, 1, TILE), jnp.int32),
            ((w, tiles, 1, TILE), jnp.float32), ((w, tiles), jnp.int32),
            ((w, tiles), jnp.int32), ((w, 1), jnp.int32), *counted]


@functools.partial(jax.jit, static_argnames="act")
def _bwd_fleet(x, wg, wu, wd, pos, g, u, token, scale, group, rows, used,
               dout, *, act):
    """-> the cotangents of x [W, N, d] and weight [W, N, E], float32,
    and of the experts' leaves [W, E, ...] in the leaves' dtype."""
    w, e = wg.shape[:2]
    layout = (*(_flat(a) for a in (token, scale, group, rows)), used[0])
    g, u = _flat(g), _flat(u)
    dx, dscale, dg, du = _backward_dx(
        _rows_of(dout), g, u, _flat(wg), _flat(wu), _flat(wd), *layout,
        act=act, interpret=_interpret())
    dwg, dwu, dwd = _backward_dw(
        _rows_of(x), _rows_of(dout), g, u, dg, du, *layout, groups=w * e,
        act=act, interpret=_interpret())
    with jax.named_scope("dopt_route"):
        dweight = slot_values(dscale, pos)
    return (dx.reshape(x.shape), dweight, *(
        a.reshape(w, e, *a.shape[1:]).astype(leaf.dtype)
        for a, leaf in ((dwg, wg), (dwu, wu), (dwd, wd))))


def _bwd_shapes(x, wg, wu, wd, pos, *_):
    return [(x.shape, jnp.float32), (pos.shape, jnp.float32),
            *((a.shape, a.dtype) for a in (wg, wu, wd))]


@functools.lru_cache(maxsize=None)
def _fused(k: int, dtype, keep_name, act: str = "silu"):
    """(x, hit, weight, wg, wu, wd, init) -> (init + the held experts'
    part, over arrays that carry their worker axis (``_fwd_fleet``),
    differentiable in all but ``hit``; for ``relu`` each worker's
    ``zero_share``, else None); the gate and up products and the layout's
    small arrays under ``keep_name`` for a ``jax.checkpoint`` to keep, so
    that its backward pass runs neither the forward kernel nor the layout
    again."""
    config = dict(k=k, dtype=dtype, act=act)
    counted = act == "relu"
    fwd = _fleet("dopt_experts_fwd", functools.partial(_fwd_fleet, **config),
                 "tttwwwt", "ttwwwwwww" + "w" * counted,
                 functools.partial(_fwd_shapes, **config))
    bwd = _fleet("dopt_experts_bwd", functools.partial(_bwd_fleet, act=act),
                 "twwwtwwwwwwwt", "ttwww", _bwd_shapes)

    def run(x, hit, weight, wg, wu, wd, init):
        out, *kept = fwd(x, hit, weight, wg, wu, wd, init)
        share = kept.pop() if counted else None
        return out, share, kept

    @jax.custom_vjp
    def experts(x, hit, weight, wg, wu, wd, init):
        return run(x, hit, weight, wg, wu, wd, init)[:2]

    def forward(x, hit, weight, wg, wu, wd, init):
        out, share, kept = run(x, hit, weight, wg, wu, wd, init)
        if keep_name is not None:
            kept = [checkpoint_name(a, keep_name) for a in kept]
        return (out, share), (x, wg, wu, wd, *kept)

    def backward(residuals, cotangents):
        dout = cotangents[0]
        dx, dweight, dwg, dwu, dwd = bwd(*residuals, dout)
        return dx, None, dweight, dwg, dwu, dwd, dout

    experts.defvjp(forward, backward)
    return experts


def _grouped(x, hit, weight, wg, wu, wd, init, *, k, dtype, keep_name,
             act="silu"):
    """The sorted slots in ``jax.numpy``, one worker's row (no worker
    axis: ``vmap`` batches it as it is): x and init [N, d], hit and
    weight [N, E], the experts' leaves [E, ...] -> (init + the held
    experts' part, for ``relu`` the row's ``zero_share`` else None).  The
    same values under ``keep_name`` as the kernels': the layout and the
    two products."""
    keep = ((lambda a: checkpoint_name(a, keep_name)) if keep_name
            else (lambda a: a))
    e = hit.shape[1]
    with jax.named_scope("dopt_route"):
        _, token, scale, group, rows, _ = map(
            keep, slot_layout(hit[None], weight[None], k=k))
        token = token.reshape(-1)
        mine = (jnp.repeat(group, TILE)[None, :] == jnp.arange(e)[:, None])
        real = (jnp.arange(TILE)[None, :] < rows[:, None]).reshape(-1)
        xs = jnp.where(real[:, None], x[token], 0.0).astype(dtype)  # [P, d]
    xs = jnp.where(mine[:, :, None], xs[None], 0)             # [E, P, d]
    g = keep(jnp.einsum("epd,edf->pf", xs, wg.astype(dtype)))
    u = keep(jnp.einsum("epd,edf->pf", xs, wu.astype(dtype)))
    # (the weight goes in BEFORE the down product: its gradient then asks
    # for the activation, and not for a product the recompute would run)
    with jax.named_scope("dopt_route"):
        mid = ACTIVATIONS[act](g) * u * scale.reshape(-1, 1).astype(dtype)
    mid = jnp.where(mine[:, :, None], mid[None], 0)
    y = jnp.einsum("epf,efd->pd", mid, wd.astype(dtype),
                   preferred_element_type=jnp.float32)
    share = (zero_share(g, rows, jnp.zeros_like(rows), 1)[0]
             if act == "relu" else None)
    with jax.named_scope("dopt_route"):
        return init.at[token].add(y), share


def grouped_experts(x, hit, weight, experts, init, *, k: int, dtype,
                    keep_name=None, act: str = "silu"):
    """(``init`` + the held experts' part of one row of tokens, the share
    of ``zero_share``'s zeros for ``act`` ``relu`` and None for ``silu``):
    x and init [N, d] float32, hit [N, E] bool (the token was routed to
    that held expert) and weight [N, E] float32 (its combine weight there,
    0 elsewhere), ``experts`` the leaves ``gate``, ``up`` [E, d, F] and
    ``down`` [E, F, d] (float32, or the parameters' dtype: their
    gradients come back in it), ``k`` the experts a token is routed to
    (the most of ``hit`` a row), ``dtype`` the matmuls' input dtype,
    ``act`` the gate's activation (``ACTIVATIONS``: ``down(act(x gate) *
    (x up))``).  Exact for any routing.  ``path`` says which body runs;
    under the engines' ``vmap`` over rows the kernels' share is of the
    worker's rows together, the ``jax.numpy`` body's of each row."""
    leaves = tuple(experts[name] for name in ("gate", "up", "down"))
    if path(x.shape[-1], leaves[0].shape[-1]) == "grouped":
        return _grouped(x, hit, weight, *leaves, init, k=k, dtype=dtype,
                        keep_name=keep_name, act=act)
    lead = lambda a: a[None]
    out, share = _fused(k, jnp.dtype(dtype), keep_name, act)(
        *map(lead, (x, hit, weight, *leaves, init)))
    return out[0], None if share is None else share[0]
