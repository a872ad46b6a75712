"""Pallas TPU kernels for a learned sparse attention: one block of
queries against the keys it can see, the lightning indexer's scores of
those keys and, under the mask the selection makes of them (it comes
from the DATA), the attention's body (``dopt.models.decoder``'s
``_index_scores`` and ``_masked_attention`` are the definitions they are
held to, ``tests/test_decoder.py``).

Three kernels of the body, and no score ever leaves VMEM:

* ``..._fwd``: q [G, R, Tq, D], k and v [G, Tk, D] and ONE mask for all
  G * R heads -> the attention output and the rows' log-sum-exp, by an
  online float32 softmax over tiles of keys;
* ``..._probs``: given the log-sum-exp, the normalised probabilities
  summed over all G * R heads inside the kernel, divided by their
  number: one float32 [Tq, Tk] array (the alignment term's target);
* ``..._bwd``: dq, dk and dv from the output's cotangent, the scores
  computed again in VMEM under the same mask.

Arrangement.  A grid step holds one tile of keys and the R query heads
that share its key/value head, their queries side by side: q is read as
[R * Tq, D] and the scores are formed TRANSPOSED, [keys, R * Tq].  A
query's maximum, sum, log-sum-exp and ``delta`` are then rows of
[1, R * Tq] that broadcast along sublanes, the reductions run over
sublanes (elementwise across vregs), no loop over heads is written (a
product over R * Tq sums dk and dv over the heads by itself) and the
statistics leave the kernel as dense rows; what accumulates per query
(the output, dq) is held as [D, R * Tq] and turned once at the end.  The
mask arrives transposed, int8 [Tk, Tq], as an ordinary blocked input, so
a ``vmap`` (the engines' worker axis) only adds a grid axis.  The one
prefetched scalar is the index of the last tile of keys the block sees
(``last_tile``, from the position of its first query): tiles after it are
not visited, and their index maps repeat the last visited tile, so
nothing is fetched for them either.  The three wrappers are jitted, so a
round that calls them a hundred times traces each shape once; a kernel's
body is two dozen operations because each one is traced, batched and
lowered again at every call that is left (``setup_s``; PERF.md, PR 33).

Two kernels of the index scores, ``index[q, k] = sum_j wi[q, j] *
relu(qi[j, q] . ki[k])``, in the same arrangement (all J indexer heads'
queries side by side, qi read as [J * Tq, E], the products transposed
[keys, J * Tq], ``wi`` a [1, J * Tq] row, the head sum an add of J lane
slices), so that no [J, Tq, keys] array ever leaves VMEM:

* ``dopt_attn_dopt_index_fwd``: the float32 [Tq, Tk] scores, zeros in
  the tiles past the block's last query (forward, and again in a
  block's ``jax.checkpoint`` recompute);
* ``dopt_attn_dopt_index_bwd``: from the scores' cotangent (the
  alignment term's, zero off the selected keys) the products again in
  VMEM, ``g = dindex * wi * (dots > 0)`` rounded to the compute dtype
  for its two products (what a float32 cotangent into a default-
  precision product with a bfloat16 operand is), ``dqi = g . ki`` held
  per query across the tiles, ``dki = g^T . qi`` a tile's rows, and
  ``dwi = sum_k relu(dots) * dindex`` in float32.

A masked position contributes exactly 0; every row keeps at least one
key (a query attends itself), so no row is empty.  Matmul inputs in the
compute dtype with float32 accumulation; the probabilities are rounded
to the compute dtype for the value product.  The products' precision is
pinned to ``DEFAULT``: bfloat16 products are exact in float32 at any
precision, and Mosaic refuses bfloat16 operands at the ``highest`` a
parity check sets around the whole program (PERF.md, PR 28).

A trace's event carries its instruction's name alone, and the name stack
the benchmark's readers join to it comes from the compiled HLO's
metadata, which a custom call may lack: so the kernels' own names spell
out both scopes they stand in, ``dopt_attn`` and ``dopt_attend`` or
``dopt_index`` (and not the other's: each scope's readers divide by the
time under it).  Compiled on ``tpu``, interpreted on ``cpu``
(``dopt.ops.pallas_interpret``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


KERNEL_NAMES = {kind: f"dopt_attn_dopt_attend_{kind}"
                for kind in ("fwd", "probs", "bwd")}
# Keys a grid step (where the extent is not a multiple of it, their
# greatest common divisor: a multiple of 128 wherever ``fits``).  At 256
# a step's float32 [tile, R * Tq] arrays (2 MB each at the benchmark's
# cell) fit the 16 MiB of VMEM a kernel gets by default.  512 and 1,024
# run the kernels 4-7% faster alone, but ask for a raised limit, under
# which XLA reserves 60 MB more of HBM for the round, and are 29 MB more
# of code, which the chip holds in HBM too (PERF.md, PR 33).
KEY_TILE = 256
INDEX_KERNEL_NAMES = {kind: f"dopt_attn_dopt_index_{kind}"
                      for kind in ("fwd", "bwd")}
# The most lanes (indexer heads side by side x queries) the index
# kernels take: a step's float32 [KEY_TILE, lanes] arrays, 4 MB each at
# the benchmark's cell, are what their VMEM holds (tiles of 512 keys run
# them 1-3% faster alone, 128 8-12% slower; PERF.md, PR 35).
INDEX_LANES = 4096
_LANES = 128
# Stands in for -inf under the online maximum: exp(_MASKED - m) is exactly
# 0 for any real m, and _MASKED - _MASKED is 0, not nan.
_MASKED = -1e30
_NT = (((1,), (1,)), ((), ()))     # a @ b.T


def fits(block: int, keys: int, head_dim: int) -> bool:
    """Whether the kernels take a block of ``block`` queries against
    ``keys`` keys with heads of ``head_dim``: all three whole lane tiles."""
    return (block % _LANES == 0 and head_dim % _LANES == 0
            and keys % _LANES == 0 and keys >= block)


def _interpret() -> bool:
    """``dopt.ops.pallas_interpret()``, looked up at the call: the tests
    and the compile-only sizing of a round patch it there."""
    from dopt import ops

    return ops.pallas_interpret()


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    return jax.lax.dot_general(a, b, dims, precision=jax.lax.Precision.DEFAULT,
                               preferred_element_type=jnp.float32)


def _key_tile(keys: int) -> int:
    """Keys a grid step against ``keys`` keys in all."""
    return math.gcd(keys, KEY_TILE)


def last_tile(first, tq: int, keys: int):
    """int32 [1]: index of the last tile of keys that a block of ``tq``
    queries starting at position ``first`` sees (the kernels' prefetched
    scalar: tiles after it are not visited)."""
    return ((jnp.asarray(first, jnp.int32) + (tq - 1))
            // _key_tile(keys)).reshape(1)


def _visited(j, last_ref):
    """Tile ``j``'s index for an index map: itself up to the last tile the
    block sees, that one again after it (nothing new to fetch)."""
    return jnp.minimum(j, last_ref[0])


def _scores(k, q, keep, scale):
    """[tk, R * Tq] float32 scores of a group's R heads, queries of every
    head side by side in the lanes, ``_MASKED`` where not kept: k
    [tk, D], q [R * Tq, D], keep [tk, R * Tq]."""
    return jnp.where(keep, _dot(k, q, _NT) * scale, _MASKED)


def _keep_of(keep_ref, heads: int):
    """The tile's int8 [tk, Tq] mask as bool, once for each of the
    group's heads along the lanes."""
    keep = keep_ref[...].astype(jnp.float32)
    return jnp.concatenate([keep] * heads, axis=1) > 0


# ------------------------------------------------------------------ forward

def _fwd_kernel(last_ref, q_ref, k_ref, v_ref, keep_ref, out_ref, lse_ref,
                m_ref, l_ref, acc_ref, *, scale):
    heads, tq, d = q_ref.shape
    tk = k_ref.shape[0]
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, _MASKED)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j <= last_ref[0])
    def _():
        s = _scores(k_ref[...], q_ref[...].reshape(heads * tq, d),
                    _keep_of(keep_ref, heads), scale)
        m_prev = m_ref[...]                                  # [1, R * Tq]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
        # a row that has kept no key yet (m_new = _MASKED) gathers ones
        # here; its first kept key multiplies them by exactly 0
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=0, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + _dot(
            v_ref[...].T, p.astype(v_ref.dtype))             # [D, R * Tq]
        m_ref[...] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        out = (acc_ref[...] / l_ref[...]).T                  # [R * Tq, D]
        out_ref[...] = out.reshape(heads, tq, d).astype(out_ref.dtype)
        lse_ref[...] = m_ref[...] + jnp.log(l_ref[...])


@functools.partial(jax.jit, static_argnames="interpret")
def _forward(q, k, v, keep_t, last, *, interpret: bool):
    g, r, tq, d = q.shape
    tk = _key_tile(k.shape[1])

    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=1.0 / math.sqrt(d)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(g, k.shape[1] // tk),
            in_specs=[
                pl.BlockSpec((None, r, tq, d), lambda g, j, f: (g, 0, 0, 0)),
                pl.BlockSpec((None, tk, d), lambda g, j, f: (g, _visited(j, f), 0)),
                pl.BlockSpec((None, tk, d), lambda g, j, f: (g, _visited(j, f), 0)),
                pl.BlockSpec((tk, tq), lambda g, j, f: (_visited(j, f), 0)),
            ],
            out_specs=[
                pl.BlockSpec((None, r, tq, d), lambda g, j, f: (g, 0, 0, 0)),
                pl.BlockSpec((None, 1, r * tq), lambda g, j, f: (g, 0, 0)),
            ],
            scratch_shapes=[pltpu.VMEM((1, r * tq), jnp.float32),
                            pltpu.VMEM((1, r * tq), jnp.float32),
                            pltpu.VMEM((d, r * tq), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((g, 1, r * tq), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=KERNEL_NAMES["fwd"],
    )(last, q, k, v, keep_t)
    return out, lse.reshape(g, r, tq)


# ---------------------------------------------- head-summed probabilities

def _probs_kernel(last_ref, q_ref, k_ref, lse_ref, keep_ref, out_ref,
                  acc_ref, *, scale):
    groups, heads, tq, d = q_ref.shape
    tk = k_ref.shape[1]
    j = pl.program_id(0)

    @pl.when(j <= last_ref[0])
    def _():
        keep = _keep_of(keep_ref, heads)
        acc_ref[...] = jnp.zeros_like(acc_ref)

        def group(g, carry):
            s = _scores(k_ref[g], q_ref[g].reshape(heads * tq, d), keep,
                        scale)
            p = jnp.exp(s - lse_ref[g])                      # [tk, R * Tq]
            acc_ref[...] += sum(p[:, r * tq:(r + 1) * tq]
                                for r in range(heads))
            return carry

        jax.lax.fori_loop(0, groups, group, None)
        out_ref[...] = (acc_ref[...] * (1.0 / (groups * heads))).T

    @pl.when(j > last_ref[0])
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)


@functools.partial(jax.jit, static_argnames="interpret")
def head_mean_probs(q, k, lse, keep_t, last, *, interpret: bool):
    """float32 [Tq, Tk]: ``exp(score - lse)`` where kept, 0 elsewhere, the
    mean over all G * R heads: q [G, R, Tq, D], k [G, Tk, D], lse
    [G, R, Tq], keep_t int8 [Tk, Tq], last int32 [1] (``last_tile``).  Not
    differentiated (the alignment term's target is a constant)."""
    g, r, tq, d = q.shape
    tk = _key_tile(k.shape[1])

    return pl.pallas_call(
        functools.partial(_probs_kernel, scale=1.0 / math.sqrt(d)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(k.shape[1] // tk,),
            in_specs=[
                pl.BlockSpec((g, r, tq, d), lambda j, f: (0, 0, 0, 0)),
                pl.BlockSpec((g, tk, d), lambda j, f: (0, _visited(j, f), 0)),
                pl.BlockSpec((g, 1, r * tq), lambda j, f: (0, 0, 0)),
                pl.BlockSpec((tk, tq), lambda j, f: (_visited(j, f), 0)),
            ],
            out_specs=pl.BlockSpec((tq, tk), lambda j, f: (0, j)),
            scratch_shapes=[pltpu.VMEM((tk, tq), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((tq, k.shape[1]), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=KERNEL_NAMES["probs"],
    )(last, q, k, lse.reshape(g, 1, r * tq), keep_t)


# ----------------------------------------------------------------- backward

def _bwd_kernel(last_ref, q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref,
                keep_ref, dq_ref, dk_ref, dv_ref, dq_acc, *, scale):
    heads, tq, d = q_ref.shape
    tk = k_ref.shape[0]
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    @pl.when(j <= last_ref[0])
    def _():
        k, v = k_ref[...], v_ref[...]
        q = q_ref[...].reshape(heads * tq, d)
        do = do_ref[...].reshape(heads * tq, d)
        p = jnp.exp(_scores(k, q, _keep_of(keep_ref, heads), scale)
                    - lse_ref[...])                          # [tk, R * Tq]
        # (the products over R * Tq sum over the group's heads too)
        dv_ref[...] = _dot(p.astype(do.dtype), do).astype(dv_ref.dtype)
        dp = _dot(v, do, _NT)
        ds = (p * (dp - delta_ref[...]) * scale).astype(q.dtype)
        dk_ref[...] = _dot(ds, q).astype(dk_ref.dtype)
        dq_acc[...] += _dot(k.T, ds)                         # [D, R * Tq]

    @pl.when(j > last_ref[0])
    def _():
        dk_ref[...] = jnp.zeros_like(dk_ref)
        dv_ref[...] = jnp.zeros_like(dv_ref)

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        dq_ref[...] = dq_acc[...].T.reshape(heads, tq, d).astype(dq_ref.dtype)


@functools.partial(jax.jit, static_argnames="interpret")
def _backward(q, k, v, keep_t, last, do, lse, delta, *, interpret: bool):
    g, r, tq, d = q.shape
    tk = _key_tile(k.shape[1])

    heads = pl.BlockSpec((None, r, tq, d), lambda g, j, f: (g, 0, 0, 0))
    rows = pl.BlockSpec((None, 1, r * tq), lambda g, j, f: (g, 0, 0))
    keys = pl.BlockSpec((None, tk, d), lambda g, j, f: (g, _visited(j, f), 0))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, scale=1.0 / math.sqrt(d)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(g, k.shape[1] // tk),
            in_specs=[heads, heads, rows, rows, keys, keys,
                      pl.BlockSpec((tk, tq), lambda g, j, f: (_visited(j, f), 0))],
            out_specs=[
                heads,
                pl.BlockSpec((None, tk, d), lambda g, j, f: (g, j, 0)),
                pl.BlockSpec((None, tk, d), lambda g, j, f: (g, j, 0)),
            ],
            scratch_shapes=[pltpu.VMEM((d, r * tq), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=KERNEL_NAMES["bwd"],
    )(last, q, do, lse.reshape(g, 1, r * tq), delta.reshape(g, 1, r * tq),
      k, v, keep_t)


# ------------------------------------------------- the indexer's scores

def index_fits(block: int, keys: int, heads: int) -> bool:
    """Whether the index kernels take a block of ``block`` queries of
    ``heads`` indexer heads against ``keys`` keys: block and keys whole
    lane tiles, and all the heads' queries side by side within
    ``INDEX_LANES`` (a step's float32 [tile, heads * block] arrays are
    what their VMEM holds).  Any head size: it is the contraction."""
    return (block % _LANES == 0 and keys % _LANES == 0 and keys >= block
            and heads * block <= INDEX_LANES)


def _index_fwd_kernel(last_ref, q_ref, k_ref, w_ref, out_ref):
    tq = out_ref.shape[0]
    j = pl.program_id(0)

    @pl.when(j <= last_ref[0])
    def _():
        s = jnp.maximum(_dot(k_ref[...], q_ref[...], _NT), 0.0) * w_ref[...]
        out_ref[...] = sum(s[:, h * tq:(h + 1) * tq]
                           for h in range(s.shape[1] // tq)).T

    @pl.when(j > last_ref[0])
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)


@functools.partial(jax.jit, static_argnames="interpret")
def _index_forward(qi, ki, wi, last, *, interpret: bool):
    j, tq, e = qi.shape
    keys = ki.shape[0]
    tk = _key_tile(keys)
    return pl.pallas_call(
        _index_fwd_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(keys // tk,),
            in_specs=[
                pl.BlockSpec((j * tq, e), lambda j, f: (0, 0)),
                pl.BlockSpec((tk, e), lambda j, f: (_visited(j, f), 0)),
                pl.BlockSpec((1, j * tq), lambda j, f: (0, 0)),
            ],
            out_specs=pl.BlockSpec((tq, tk), lambda j, f: (0, j))),
        out_shape=jax.ShapeDtypeStruct((tq, keys), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=INDEX_KERNEL_NAMES["fwd"],
    )(last, qi.reshape(j * tq, e), ki, wi.T.reshape(1, j * tq))


def _index_bwd_kernel(last_ref, q_ref, k_ref, w_ref, d_ref, dq_ref, dk_ref,
                      dw_ref, dq_acc):
    tq = d_ref.shape[0]
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)
        dw_ref[...] = jnp.zeros_like(dw_ref)

    @pl.when(j <= last_ref[0])
    def _():
        k, q = k_ref[...], q_ref[...]
        s = _dot(k, q, _NT)                                  # [tk, J * Tq]
        d = jnp.concatenate([d_ref[...].T] * (s.shape[1] // tq), axis=1)
        dw_ref[...] += jnp.sum(jnp.maximum(s, 0.0) * d, axis=0,
                               keepdims=True)
        g = jnp.where(s > 0, d * w_ref[...], 0.0).astype(q.dtype)
        dk_ref[...] = _dot(g, q).astype(dk_ref.dtype)
        dq_acc[...] += _dot(k.T, g)                          # [E, J * Tq]

    @pl.when(j > last_ref[0])
    def _():
        dk_ref[...] = jnp.zeros_like(dk_ref)

    @pl.when(j == pl.num_programs(0) - 1)
    def _():
        dq_ref[...] = dq_acc[...].T.astype(dq_ref.dtype)


@functools.partial(jax.jit, static_argnames="interpret")
def _index_backward(qi, ki, wi, last, dindex, *, interpret: bool):
    j, tq, e = qi.shape
    keys = ki.shape[0]
    tk = _key_tile(keys)
    heads = pl.BlockSpec((j * tq, e), lambda j, f: (0, 0))
    row = pl.BlockSpec((1, j * tq), lambda j, f: (0, 0))
    dq, dk, dw = pl.pallas_call(
        _index_bwd_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(keys // tk,),
            in_specs=[
                heads,
                pl.BlockSpec((tk, e), lambda j, f: (_visited(j, f), 0)),
                row,
                pl.BlockSpec((tq, tk), lambda j, f: (0, _visited(j, f))),
            ],
            out_specs=[heads, pl.BlockSpec((tk, e), lambda j, f: (j, 0)), row],
            scratch_shapes=[pltpu.VMEM((e, j * tq), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((j * tq, e), qi.dtype),
                   jax.ShapeDtypeStruct(ki.shape, ki.dtype),
                   jax.ShapeDtypeStruct((1, j * tq), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=INDEX_KERNEL_NAMES["bwd"],
    )(last, qi.reshape(j * tq, e), ki, wi.T.reshape(1, j * tq), dindex)
    return dq.reshape(qi.shape), dk, dw.reshape(j, tq).T


@jax.custom_vjp
def _index(qi, ki, wi, last):
    return _index_forward(qi, ki, wi, last, interpret=_interpret())


def _index_fwd(qi, ki, wi, last):
    return _index(qi, ki, wi, last), (qi, ki, wi, last)


def _index_bwd(residuals, dindex):
    return *_index_backward(*residuals, dindex, interpret=_interpret()), None


_index.defvjp(_index_fwd, _index_bwd)


def index_scores(qi, ki, wi, first):
    """The lightning indexer's scores of one block of queries, at
    positions ``first ...``, against the keys ``0 .. Tk-1``: ``index[q, k]
    = sum_j wi[q, j] * relu(qi[j, q] . ki[k])``, float32 [Tq, Tk], from qi
    [J, Tq, E] and ki [Tk, E] in the compute dtype and wi [Tq, J] float32;
    differentiable in all three.  Exact zeros in the tiles of keys past
    the block's last query, which are not visited (forward or backward:
    no gradient comes from there or goes there); inside the last visited
    tile the scores of keys a query cannot see are computed like any
    other and are the caller's to mask."""
    last = last_tile(first, qi.shape[1], ki.shape[0])
    return _index(qi, ki, wi, last)


# ------------------------------------------------------------------ surface

def _attend_bwd(residuals, cotangents):
    q, k, v, keep_t, last, out, lse = residuals
    do, dlse = cotangents
    # d lse / d score is the probability, as is the factor on delta
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1) - dlse
    dq, dk, dv = _backward(q, k, v, keep_t, last, do, lse, delta,
                            interpret=_interpret())
    return dq, dk, dv, None, None


@functools.lru_cache(maxsize=None)
def _attend(residual_name):
    """(q, k, v, keep_t, last) -> (output, log-sum-exp), differentiable
    in q, k and v, its residuals under ``residual_name``."""

    @jax.custom_vjp
    def attend(q, k, v, keep_t, last):
        return _forward(q, k, v, keep_t, last, interpret=_interpret())

    def forward(q, k, v, keep_t, last):
        out, lse = _forward(q, k, v, keep_t, last, interpret=_interpret())
        if residual_name is not None:
            out = checkpoint_name(out, residual_name)
            lse = checkpoint_name(lse, residual_name)
        return (out, lse), (q, k, v, keep_t, last, out, lse)

    attend.defvjp(forward, _attend_bwd)
    return attend


def masked_attention(q, k, v, keep, first, *, residual_name=None):
    """One block of queries, at positions ``first ...``, against the keys
    ``0 .. Tk-1`` where ``keep``: q [G, R, Tq, D], k and v [G, Tk, D] in
    the compute dtype, keep [Tq, Tk] bool with no key kept past its
    query and at least one a row, ``first`` an int32 scalar -> (output
    [G, R, Tq, D], the head-mean [Tq, Tk] float32 of the probabilities,
    a constant under differentiation).

    The output and the log-sum-exp carry ``residual_name`` for a
    ``jax.checkpoint`` to keep, so that its backward pass does not run
    the forward kernel again."""
    keep_t = keep.T.astype(jnp.int8)
    last = last_tile(first, q.shape[2], k.shape[1])
    out, lse = _attend(residual_name)(q, k, v, keep_t, last)
    target = head_mean_probs(*jax.lax.stop_gradient((q, k, lse)), keep_t,
                             last, interpret=_interpret())
    return out, target
