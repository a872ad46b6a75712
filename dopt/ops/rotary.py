"""Pallas TPU kernels for the rotary embedding between a decoder
attention's projections and the attention: ONE pass a tensor that reads
the projection's float32 product once, rotates its rotary lanes, passes
the rest through, applies the queries' scale and writes the tensor in
the compute dtype the attention takes, once (``dopt.models.decoder``'s
``_rotary`` with the scale and the cast after it is the definition they
are held to, ``tests/test_rotary.py``).

Two forms of the one kernel:

* ``rotate``: x [H, T, D] float32 -> [H, T, D] in the compute dtype:
  the lanes ``lo .. lo + rotary`` rotated, every lane times ``scale``
  (a query's 1/sqrt(D) where the fused attention takes it, 1 for a key);
* ``latent_keys``: a latent attention's keys and values from ONE pass
  over the kv up-projection's product [H, T, nope + V] and the rotary
  key [T, rotary] that every head shares: k [H, T, nope + rotary] (each
  head's ``nope`` lanes beside the shared key, rotated once a position
  and broadcast over the heads in VMEM) and v [H, T, V].

A rotation is ``x * cos + partner(x) * sin`` lane by lane, where
``partner`` swaps the two members of each pair: lanes ``(i, i + r / 2)``
(``rotate_half``) or ``(2i, 2i + 1)`` (interleaved pairs), by two lane
rolls and a select (one roll where the pairs are halves of the whole
region).  The sign of ``rotate_half``'s first half lives in the sin
table, and the tables (``_lane_tables``, [T, region] float32: the
decoder's cos / sin of each position's angles, repeated over each pair,
times the attention factor; 1 and 0 on a region's lanes past the rotary
ones) are computed once a call outside the kernel and broadcast over the
heads in VMEM, never recomputed a head.  The scale multiplies the
rotated value, as the definition's divides it.  Interleaved
pairs stay interleaved: the lanes keep the product's order, the same
order for every query and key, so every score is the interleaved one.

The backward pass is the same arithmetic with ``-sin`` (the rotation's
transpose: ``partner`` is its own inverse and a pair shares its angle):
it reads the cotangent in the compute dtype once and writes the float32
cotangent of the product once.  ``latent_keys``'s backward sums the
heads' cotangents of the shared key in float32 inside the kernel, a
block of heads a step on the grid's last axis, and rotates the sum back
once a position.  No residual: the tables are all the backward asks for.

A trace's event carries its instruction's name alone, and a custom call
may lack the name stack the benchmark's readers join to it: so the
kernels' own names spell out both scopes they stand in, ``dopt_attn``
and ``dopt_rope`` (``KERNEL_NAMES``).  Compiled on ``tpu``, interpreted
on ``cpu`` (``dopt.ops.pallas_interpret``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

KERNEL_NAMES = {kind: f"dopt_attn_dopt_rope_{kind}" for kind in ("fwd", "bwd")}
# Positions a grid step (where the row is not a multiple of it, their
# greatest common divisor: a multiple of 128 wherever ``fits``), and the
# most float32 bytes a step reads (lanes as VMEM holds them, whole tiles
# of 128): a block of heads that large, double-buffered beside its
# output, stays inside the 16 MiB of VMEM a kernel gets by default.
ROW_TILE = 512
STEP_BYTES = 2 << 20
_LANES = 128


def fits(t: int, head_dim: int, rotary_dim: int, nope: int = 0) -> bool:
    """Whether the kernels take rows of ``t`` positions and heads of
    ``head_dim`` lanes whose ``rotary_dim`` rotary lanes follow ``nope``
    lanes without rotary: the row whole tiles of 128 positions, the head
    at least one whole lane tile, rotary lanes a multiple of 64 that
    start on a lane tile and end on one or at the head's end."""
    end = nope + rotary_dim
    return (t % _LANES == 0 and head_dim >= _LANES and head_dim % 64 == 0
            and rotary_dim % 64 == 0 and rotary_dim > 0
            and nope % _LANES == 0 and end <= head_dim
            and (end == head_dim or _whole(end) <= head_dim))


def _whole(lanes: int) -> int:
    """``lanes`` rounded up to whole lane tiles."""
    return -(-lanes // _LANES) * _LANES


def _interpret() -> bool:
    """``dopt.ops.pallas_interpret()``, looked up at the call: the tests
    and the compile-only sizing of a round patch it there."""
    from dopt import ops

    return ops.pallas_interpret()


def _lane_tables(cos, sin, *, interleave: bool, width: int):
    """The kernels' (cos, signed sin) [T, width] float32 from one
    position's [T, r / 2] cos and sin of its angles (the attention factor
    already in them): each value on both lanes of its pair, the sin
    negative on a pair's first member; lanes ``r .. width`` hold 1 and 0
    (passed through)."""
    t, half = cos.shape
    if interleave:
        c = jnp.repeat(cos, 2, axis=-1)
        s = jnp.stack([-sin, sin], axis=-1).reshape(t, 2 * half)
    else:
        c = jnp.concatenate([cos, cos], axis=-1)
        s = jnp.concatenate([-sin, sin], axis=-1)
    pad = width - 2 * half
    c = jnp.pad(c, ((0, 0), (0, pad)), constant_values=1.0)
    s = jnp.pad(s, ((0, 0), (0, pad)))
    return c, s


def _partner(x, pair: int):
    """Each lane's pair member along the last axis: ``x[j + pair]`` for a
    pair's first member (``j % (2 pair) < pair``), ``x[j - pair]`` for its
    second."""
    axis, width = x.ndim - 1, x.shape[-1]
    up = pltpu.roll(x, pair, axis)                    # x[j - pair]
    if 2 * pair == width:
        return up
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
    return jnp.where(lane % (2 * pair) < pair,
                     pltpu.roll(x, width - pair, axis), up)


def _rotated(x, c, s, pair: int):
    return x * c + _partner(x, pair) * s


def _blocks(heads: int, t: int, lanes: int) -> tuple[int, int]:
    """(heads, positions) a grid step for [heads, t, lanes] float32."""
    tb = math.gcd(t, ROW_TILE)
    per_head = tb * _whole(lanes) * 4
    hb = max(h for h in range(1, heads + 1)
             if heads % h == 0 and h * per_head <= max(STEP_BYTES, per_head))
    return hb, tb


# ------------------------------------------------------------------ rotate

def _rotate_kernel(c_ref, s_ref, x_ref, o_ref, *, lo: int, pair: int,
                   scale: float):
    x = x_ref[...].astype(jnp.float32)                   # [hb, tb, D]
    d, w = x.shape[-1], c_ref.shape[-1]

    def scaled(y):
        return (y if scale == 1.0 else y * scale).astype(o_ref.dtype)

    o_ref[..., lo:lo + w] = scaled(_rotated(x[..., lo:lo + w], c_ref[...],
                                            s_ref[...], pair))
    for a, b in ((0, lo), (lo + w, d)):                  # passed through
        if a < b:
            o_ref[..., a:b] = scaled(x[..., a:b])


@functools.partial(jax.jit, static_argnames=("lo", "pair", "scale", "dtype",
                                             "kind", "interpret"))
def _rotate_call(x, c, s, *, lo, pair, scale, dtype, kind, interpret):
    h, t, d = x.shape
    hb, tb = _blocks(h, t, d)
    table = pl.BlockSpec((tb, c.shape[-1]), lambda i, j: (i, 0))
    heads = pl.BlockSpec((hb, tb, d), lambda i, j: (j, i, 0))
    return pl.pallas_call(
        functools.partial(_rotate_kernel, lo=lo, pair=pair, scale=scale),
        grid=(t // tb, h // hb),
        in_specs=[table, table, heads],
        out_specs=heads,
        out_shape=jax.ShapeDtypeStruct(x.shape, dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=KERNEL_NAMES[kind],
    )(c, s, x)


@functools.lru_cache(maxsize=None)
def _rotate(lo: int, pair: int, scale: float, dtype):
    """(x, c, s) -> the rotated, scaled tensor in ``dtype``,
    differentiable in x."""

    def call(x, c, s, kind, out):
        return _rotate_call(x, c, s, lo=lo, pair=pair, scale=scale,
                            dtype=out, kind=kind, interpret=_interpret())

    @jax.custom_vjp
    def rotate(x, c, s):
        return call(x, c, s, "fwd", dtype)

    def forward(x, c, s):
        return rotate(x, c, s), (c, s)

    def backward(tables, g):
        c, s = tables
        return call(g, c, -s, "bwd", jnp.float32), None, None

    rotate.defvjp(forward, backward)
    return rotate


def rotate(x, cos, sin, *, rotary_dim: int, interleave: bool, scale: float,
           dtype, nope: int = 0):
    """x [H, T, D] float32 (a projection's product) -> [H, T, D] in
    ``dtype``: lanes ``nope .. nope + rotary_dim`` rotated by the
    positions' angles (``cos``, ``sin`` [T, rotary_dim / 2] float32, the
    attention factor in them), the other lanes passed through, every lane
    times ``scale``; shapes ``fits`` takes.  Differentiable in x."""
    d = x.shape[-1]
    width = min(_whole(rotary_dim), d - nope)
    c, s = _lane_tables(cos, sin, interleave=interleave, width=width)
    pair = 1 if interleave else rotary_dim // 2
    return _rotate(nope, pair, float(scale), jnp.dtype(dtype))(x, c, s)


# ------------------------------------------------------------ latent keys

def _latent_fwd_kernel(c_ref, s_ref, r_ref, kv_ref, k_ref, v_ref, *,
                       nope: int, pair: int):
    kv = kv_ref[...]                                     # [hb, tb, nope + V]
    shared = _rotated(r_ref[...], c_ref[...], s_ref[...], pair)
    k_ref[..., :nope] = kv[..., :nope].astype(k_ref.dtype)
    k_ref[..., nope:] = jnp.broadcast_to(
        shared.astype(k_ref.dtype)[None],
        (kv.shape[0], *shared.shape))
    v_ref[...] = kv[..., nope:].astype(v_ref.dtype)


def _latent_bwd_kernel(c_ref, s_ref, dk_ref, dv_ref, dkv_ref, dr_ref,
                       acc_ref, *, nope: int, pair: int):
    j = pl.program_id(1)
    dk = dk_ref[...].astype(jnp.float32)                 # [hb, tb, nope + r]

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    dkv_ref[..., :nope] = dk[..., :nope]
    dkv_ref[..., nope:] = dv_ref[...].astype(jnp.float32)
    acc_ref[...] += jnp.sum(dk[..., nope:], axis=0)      # float32 head sum

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        dr_ref[...] = _rotated(acc_ref[...], c_ref[...], s_ref[...], pair)


@functools.partial(jax.jit, static_argnames=("nope", "pair", "dtype",
                                             "interpret"))
def _latent_forward(kv, r, c, s, *, nope, pair, dtype, interpret):
    h, t, lanes = kv.shape
    rd = r.shape[-1]
    hb, tb = _blocks(h, t, lanes)
    table = pl.BlockSpec((tb, rd), lambda i, j: (i, 0))

    def heads(width):
        return pl.BlockSpec((hb, tb, width), lambda i, j: (j, i, 0))

    return pl.pallas_call(
        functools.partial(_latent_fwd_kernel, nope=nope, pair=pair),
        grid=(t // tb, h // hb),
        in_specs=[table, table, table, heads(lanes)],
        out_specs=[heads(nope + rd), heads(lanes - nope)],
        out_shape=[jax.ShapeDtypeStruct((h, t, nope + rd), dtype),
                   jax.ShapeDtypeStruct((h, t, lanes - nope), dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=KERNEL_NAMES["fwd"],
    )(c, s, r, kv)


@functools.partial(jax.jit, static_argnames=("nope", "pair", "interpret"))
def _latent_backward(dk, dv, c, s, *, nope, pair, interpret):
    h, t, qk = dk.shape
    rd, lanes = qk - nope, nope + dv.shape[-1]
    hb, tb = _blocks(h, t, lanes)
    table = pl.BlockSpec((tb, rd), lambda i, j: (i, 0))

    def heads(width):
        return pl.BlockSpec((hb, tb, width), lambda i, j: (j, i, 0))

    return pl.pallas_call(
        functools.partial(_latent_bwd_kernel, nope=nope, pair=pair),
        grid=(t // tb, h // hb),
        in_specs=[table, table, heads(qk), heads(lanes - nope)],
        out_specs=[heads(lanes), table],
        out_shape=[jax.ShapeDtypeStruct((h, t, lanes), jnp.float32),
                   jax.ShapeDtypeStruct((t, rd), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((tb, rd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=KERNEL_NAMES["bwd"],
    )(c, s, dk, dv)


@functools.lru_cache(maxsize=None)
def _latent(nope: int, pair: int, dtype):
    """(kv, r, c, s) -> (k, v), differentiable in kv and r."""

    @jax.custom_vjp
    def latent(kv, r, c, s):
        return _latent_forward(kv, r, c, s, nope=nope, pair=pair,
                               dtype=dtype, interpret=_interpret())

    def forward(kv, r, c, s):
        return latent(kv, r, c, s), (c, s)

    def backward(tables, cotangents):
        c, s = tables
        dkv, dr = _latent_backward(*cotangents, c, -s, nope=nope, pair=pair,
                                   interpret=_interpret())
        return dkv, dr, None, None

    latent.defvjp(forward, backward)
    return latent


def latent_keys(kv, shared, cos, sin, *, nope: int, interleave: bool, dtype):
    """A latent attention's keys and values in ``dtype`` from kv [H, T,
    nope + V] float32 (the up-projection's product: each head's ``nope``
    lanes without rotary, then its value) and the rotary key [T, r]
    float32 every head shares: (k [H, T, nope + r], each head's lanes
    beside the shared key rotated by the positions' angles, ``cos`` /
    ``sin`` [T, r / 2]; v [H, T, V]).  Shapes ``fits`` takes for a head of
    ``nope + r``.  Differentiable in kv and the
    shared key, whose cotangent is the heads' float32 sum."""
    c, s = _lane_tables(cos, sin, interleave=interleave,
                        width=shared.shape[-1])
    pair = 1 if interleave else cos.shape[-1]
    return _latent(nope, pair, jnp.dtype(dtype))(kv, shared, c, s)
