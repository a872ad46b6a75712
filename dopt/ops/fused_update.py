"""Pallas TPU kernels for the bandwidth-bound hot op: the SGD update.

The per-step parameter update (torch semantics, ``dopt.optim.sgd_step``)

    buf ← μ·buf + g ;  p ← p − lr·buf

reads three arrays and writes two with zero FLOP reuse — pure HBM
bandwidth.  This kernel pins the fusion into ONE pass over memory
(in-place via ``input_output_aliases``) instead of trusting XLA's fusion
heuristics, and is the template for further pallas work (quantised
gossip payloads, ring-reduce mixing).

Numerics match the jnp path to fused-multiply-add association (the same
fp32 ops in the same order; only FMA contraction may differ between the
two compiled programs — ``tests/test_ops.py`` asserts 1e-6 agreement),
so the fast path stays oracle-comparable.

Layout: each leaf is viewed as a padded [rows, 128] fp32 tile grid
(lane = 128, sublane multiple of 8 — the fp32 VMEM tile), gridded over
row blocks.  The kernels compile on ``tpu`` and run in interpret mode on
``cpu`` (so CPU tests exercise the identical code path); any other
backend is an error (``pallas_interpret``).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dopt.parallel.collectives import (MIX_PRECISION, buckets_to_stacked,
                                       stacked_to_buckets)

_LANE = 128
_SUBLANE = 8
_BLOCK_ROWS = 512  # 512×128 fp32 = 256 KiB per operand block in VMEM


def pallas_interpret() -> bool:
    """The ``interpret`` argument for the default backend: False on
    ``tpu`` (Mosaic-compiled), True on ``cpu`` (the tests' interpreter).
    These are TPU kernels — any other backend raises instead of
    quietly interpreting them."""
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(
            f"dopt's Pallas kernels target TPU (interpret mode on cpu for "
            f"tests); the default backend is {backend!r} — turn "
            "fused_update off on this backend")
    return backend == "cpu"


def _make_kernel(lr: float, mu: float):
    def kernel(p_ref, m_ref, g_ref, p_out, m_out):
        buf = mu * m_ref[:] + g_ref[:]
        m_out[:] = buf
        p_out[:] = p_ref[:] - lr * buf

    return kernel


@partial(jax.jit, static_argnames=("lr", "mu", "interpret"))
def fused_sgd_momentum(p, m, g, *, lr: float, mu: float,
                       interpret: bool = False):
    """Fused momentum-SGD update of ONE array (any shape/dtype).

    Returns (new_p, new_buf) with p's shape/dtype, computed in fp32
    exactly like ``sgd_step``'s two tree.maps but in a single memory
    pass.
    """
    shape, dtype = p.shape, p.dtype
    n = p.size
    rows = -(-n // _LANE)
    if rows <= _BLOCK_ROWS:
        rows_pad = -(-rows // _SUBLANE) * _SUBLANE
        grid = 1
        block_rows = rows_pad
    else:
        rows_pad = -(-rows // _BLOCK_ROWS) * _BLOCK_ROWS
        grid = rows_pad // _BLOCK_ROWS
        block_rows = _BLOCK_ROWS

    def tile(x):
        x = x.astype(jnp.float32).reshape(-1)
        return jnp.pad(x, (0, rows_pad * _LANE - n)).reshape(rows_pad, _LANE)

    pt, mt, gt = tile(p), tile(m), tile(g)
    spec = pl.BlockSpec((block_rows, _LANE), lambda i: (i, 0),
                        memory_space=pltpu.VMEM)
    new_p, new_m = pl.pallas_call(
        _make_kernel(float(lr), float(mu)),
        out_shape=(jax.ShapeDtypeStruct(pt.shape, jnp.float32),
                   jax.ShapeDtypeStruct(mt.shape, jnp.float32)),
        grid=(grid,),
        in_specs=[spec, spec, spec],
        out_specs=(spec, spec),
        input_output_aliases={0: 0, 1: 1},
        interpret=interpret,
    )(pt, mt, gt)

    def untile(x):
        return x.reshape(-1)[:n].reshape(shape).astype(dtype)

    return untile(new_p), untile(new_m)


# ---------------------------------------------------------------------
# Fused mix + update: the gossip epilogue in one HBM pass
# ---------------------------------------------------------------------
# The D-PSGD-style gossip epilogue
#
#     p ← mix(p) − lr·buf      (mix = the [n, n] consensus contraction)
#
# reads two model-sized arrays and writes one with the only FLOPs being
# the tiny [n, n] contraction over the worker axis — like the SGD
# update above, it is pure HBM bandwidth, but XLA materialises the
# mixed intermediate between the two ops (one extra full write + read
# of |θ|).  This kernel fuses both into ONE pass over memory on the
# flat-bucket layout of ``dopt.parallel.collectives.UpdateShardSpec``
# (ROADMAP "raw speed" lever 3, the follow-on this file's header
# names): each [n, Fb] bucket slab is gridded over its flat axis, the
# f32 mixing matrix rides VMEM-resident across grid steps, and the MXU
# contraction + VPU subtract write the updated slab in place
# (``input_output_aliases``).  Numerics: matrix and accumulation in
# f32 regardless of leaf dtype — the same contract as the scatter mix
# path (tests/test_ops.py pins 1e-6 agreement with the jnp
# composition).


def _make_mix_kernel(lr: float):
    def kernel(w_ref, p_ref, m_ref, p_out):
        mixed = jnp.dot(w_ref[:], p_ref[:],
                        preferred_element_type=jnp.float32,
                        precision=MIX_PRECISION)
        p_out[:] = mixed - lr * m_ref[:]

    return kernel


@partial(jax.jit, static_argnames=("lr", "interpret"))
def fused_mix_sgd(p, buf, w, *, lr: float, interpret: bool = False):
    """Fused gossip epilogue on ONE flat bucket: ``W @ p − lr·buf``.

    ``p``/``buf`` are [n, F] stacked flat slabs (any dtype), ``w`` the
    [n, n] mixing matrix.  Returns the updated slab with p's
    shape/dtype, computed with the matrix and accumulation in f32 (the
    scatter-path numerics contract) in a single memory pass.
    """
    n, f = p.shape
    shape, dtype = p.shape, p.dtype
    n_pad = -(-n // _SUBLANE) * _SUBLANE
    # Column-block size: bound the three (n_pad, BF) f32 slabs to ~2 MiB
    # each in VMEM (the [n_pad, n_pad] matrix block is tiny beside
    # them), with the lane-multiple floor.
    bf = max((1 << 19) // max(n_pad, 1) // _LANE, 1) * _LANE
    f_pad = -(-f // bf) * bf
    grid = f_pad // bf

    def tile(x):
        x = x.astype(jnp.float32)
        return jnp.pad(x, ((0, n_pad - n), (0, f_pad - f)))

    w_t = jnp.pad(jnp.asarray(w, jnp.float32),
                  ((0, n_pad - n), (0, n_pad - n)))
    pt, mt = tile(p), tile(buf)
    w_spec = pl.BlockSpec((n_pad, n_pad), lambda i: (0, 0),
                          memory_space=pltpu.VMEM)
    spec = pl.BlockSpec((n_pad, bf), lambda i: (0, i),
                        memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        _make_mix_kernel(float(lr)),
        out_shape=jax.ShapeDtypeStruct(pt.shape, jnp.float32),
        grid=(grid,),
        in_specs=[w_spec, spec, spec],
        out_specs=spec,
        input_output_aliases={1: 0},
        interpret=interpret,
    )(w_t, pt, mt)
    return out[:n, :f].reshape(shape).astype(dtype)


def fused_mix_update(params, momentum, w_matrix, spec, *, lr: float,
                     interpret: bool | None = None):
    """The tree-level fused mix+update epilogue: flatten the stacked
    [W, ...] ``params``/``momentum`` trees into ``spec``'s buckets
    (``dopt.parallel.collectives.stacked_to_buckets``), run the fused
    ``W @ p − lr·buf`` kernel per bucket, and restore the tree.  The
    single-pass form of the D-PSGD round epilogue ``x ← Wx − lr·v`` on
    the same flat-bucket substrate the scatter hot path uses.  Both
    engines wire it behind ``fused_update="on"`` with a restructured
    scan carry: gossip carries (post-mix params, displacement buffer)
    and calls this with ``lr=1.0`` (``q_t = W·q − fbuf``); federated
    carries the theta broadcast slab and calls it with the masked-mean
    contraction matrix and ``lr=-1.0`` (``θ'_b = M·disp + θ_b``).  The
    default ``"off"`` compiles the exact pre-change programs, so the
    oracle-parity trace is untouched.

    ``interpret=None`` selects by backend (``pallas_interpret``).
    """
    if interpret is None:
        interpret = pallas_interpret()
    w = jnp.asarray(w_matrix, jnp.float32)
    pb = stacked_to_buckets(params, spec)
    mb = stacked_to_buckets(momentum, spec)
    with jax.named_scope("dopt_update"):
        out = [fused_mix_sgd(p, m, w, lr=float(lr), interpret=interpret)
               for p, m in zip(pb, mb)]
    return buckets_to_stacked(out, spec)


def mix_sgd_reference(params, momentum, w_matrix, *, lr: float):
    """Pure-jnp reference for ``fused_mix_update`` (same f32 matrix +
    accumulation; XLA materialises the mixed intermediate): the parity
    oracle the kernel is tested against."""
    w = jnp.asarray(w_matrix, jnp.float32)

    def leaf(p, m):
        mixed = jnp.tensordot(w, p.astype(jnp.float32), axes=[[1], [0]],
                              precision=MIX_PRECISION)
        return (mixed - lr * m.astype(jnp.float32)).astype(p.dtype)

    return jax.tree.map(leaf, params, momentum)


def fused_sgd_momentum_tree(params, momentum, grads, *, lr: float, mu: float,
                            interpret: bool | None = None):
    """Tree-map the fused kernel over a params pytree.

    ``interpret=None`` selects by backend (``pallas_interpret``).
    """
    if interpret is None:
        interpret = pallas_interpret()
    new_p, new_m = [], []
    p_leaves, treedef = jax.tree.flatten(params)
    m_leaves = treedef.flatten_up_to(momentum)
    g_leaves = treedef.flatten_up_to(grads)
    # dopt_update scope: phase attribution for the profiler's
    # conv/comm/update split (dopt.utils.profiling.classify_phase).
    with jax.named_scope("dopt_update"):
        for p, m, g in zip(p_leaves, m_leaves, g_leaves):
            np_, nm_ = fused_sgd_momentum(p, m, g, lr=lr, mu=mu,
                                          interpret=interpret)
            new_p.append(np_)
            new_m.append(nm_)
    return treedef.unflatten(new_p), treedef.unflatten(new_m)
