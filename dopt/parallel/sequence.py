"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

The reference has no attention and no sequence axis anywhere (SURVEY
§2.3: 2-layer CNNs on 28×28/32×32 images), so nothing here is owed for
parity — this is the framework's long-context substrate, built the TPU
way so models with a sequence dimension scale past one chip's HBM:

* ``ring_attention`` — blockwise-softmax attention with the KV shards
  rotating around the device ring via ``lax.ppermute`` (one hop per
  step, ICI neighbor traffic only).  Each device holds Q/K/V blocks of
  [B, L/D, H, Dh]; the running (max, numerator, denominator)
  flash-attention accumulators make the result exact, not approximate.
  Memory per device is O(L/D · L/D) per block pair instead of O(L²).
* ``ulysses_attention`` — the all-to-all alternative: reshard from
  sequence-sharded to head-sharded with ``all_to_all``, run exact
  attention locally over the full sequence for this device's head
  group, then reshard back.  One collective round-trip; the right
  choice when heads ≥ devices and full-sequence attention fits.

Both are pure ``shard_map`` programs over a 1-D mesh axis and are
verified elementwise against single-device dense attention in
``tests/test_sequence.py`` on a virtual 8-device CPU mesh.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

SEQ_AXIS = "sp"


def _block_attn(q, k, v, *, scale, mask=None):
    """Unnormalised blockwise attention: returns (numerator [B,Lq,H,Dh],
    denominator [B,Lq,H], rowmax [B,Lq,H]) for one KV block."""
    s = jnp.einsum("bqhd,bkhd->bqhk", q, k) * scale  # [B, Lq, H, Lk]
    if mask is not None:
        s = jnp.where(mask, s, -jnp.inf)
    m = jnp.max(s, axis=-1)                          # [B, Lq, H]
    # All-masked rows (causal block fully in the future) produce -inf
    # rowmax; zero them so exp() never sees NaN and they contribute 0.
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(s - m_safe[..., None])
    p = jnp.where(jnp.isfinite(s), p, 0.0)
    num = jnp.einsum("bqhk,bkhd->bqhd", p, v)
    den = p.sum(axis=-1)
    return num, den, m_safe


def _combine(num1, den1, m1, num2, den2, m2):
    """Merge two blockwise-softmax partial results (flash combine)."""
    m = jnp.maximum(m1, m2)
    a1 = jnp.exp(m1 - m)
    a2 = jnp.exp(m2 - m)
    num = num1 * a1[..., None] + num2 * a2[..., None]
    den = den1 * a1 + den2 * a2
    return num, den, m


@jax.checkpoint
def _block_attn_remat(q, k, v, scale, mask):
    """``_block_attn`` under rematerialisation.  Used inside the ring /
    KV-chunk scans: without remat, autodiff saves every iteration's
    [B, Lq, H, Lk] score matrix as a scan residual, so the backward pass
    holds O(L²) no matter how small the chunks are — the whole point of
    blockwise attention evaporates.  Remat recomputes the scores from
    (q, k, v) in the backward (the standard flash-attention trade:
    ~⅓ more attention FLOPs for O(block·chunk) peak memory)."""
    return _block_attn(q, k, v, scale=scale, mask=mask)


def dense_attention(q, k, v, *, causal: bool = False):
    """Single-device exact attention — the correctness reference.
    q, k, v: [B, L, H, Dh]."""
    scale = 1.0 / jnp.sqrt(q.shape[-1]).astype(q.dtype)
    s = jnp.einsum("bqhd,bkhd->bqhk", q, k) * scale
    if causal:
        lq, lk = s.shape[1], s.shape[3]
        mask = jnp.tril(jnp.ones((lq, lk), bool))
        s = jnp.where(mask[None, :, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqhk,bkhd->bqhd", p, v)


def _block_attn_chunked(qb, kb_t, vb_t, *, scale, q_pos, k_pos0, chunk):
    """Blockwise attention against one KV block, itself scanned in
    ``chunk``-sized KV slices (flash-style): peak score memory drops
    from O(Lq·Lk) to O(Lq·chunk) per device without changing the exact
    result — the running (num, den, max) accumulators combine chunks
    the same way ring steps combine blocks.  ``q_pos``/``k_pos0`` are
    global positions for exact cross-chunk causal masking (pass
    ``q_pos=None`` for non-causal)."""
    lk = kb_t.shape[1]
    nchunks = lk // chunk
    kc = kb_t.reshape(kb_t.shape[0], nchunks, chunk, *kb_t.shape[2:])
    vc = vb_t.reshape(vb_t.shape[0], nchunks, chunk, *vb_t.shape[2:])

    def chunk_step(carry, ci):
        num, den, m = carry
        kb_c = jax.lax.dynamic_index_in_dim(kc, ci, axis=1, keepdims=False)
        vb_c = jax.lax.dynamic_index_in_dim(vc, ci, axis=1, keepdims=False)
        if q_pos is not None:
            k_pos = k_pos0 + ci * chunk + jnp.arange(chunk)
            mask = (q_pos[:, None] >= k_pos[None, :])[None, :, None, :]
        else:
            mask = None
        num2, den2, m2 = _block_attn_remat(qb, kb_c, vb_c, scale, mask)
        return _combine(num, den, m, num2, den2, m2), None

    num0 = qb * 0
    den0 = jnp.sum(num0, axis=-1)
    m0 = den0 - jnp.inf
    (num, den, m), _ = jax.lax.scan(chunk_step, (num0, den0, m0),
                                    jnp.arange(nchunks))
    return num, den, m


def ring_attention(q, k, v, mesh: Mesh, *, causal: bool = False,
                   axis: str = SEQ_AXIS, kv_chunk: int | None = None):
    """Exact attention with the sequence axis sharded over ``mesh``.

    q, k, v: [B, L, H, Dh] global-view arrays (L divisible by the mesh
    size).  Device d starts with block d and receives block
    (d+1), (d+2), ... as the KV pair rotates around the ring — D-1
    ``ppermute`` hops, each overlapping the local blockwise attention.
    Causal masking is exact across blocks: query block i attends to key
    block j at full, diagonal, or zero visibility depending on i vs j.

    ``kv_chunk`` additionally scans each block's KV in chunks of that
    size (must divide the block), bounding per-device score memory at
    O(block · kv_chunk) instead of O(block²) — the knob that takes one
    device's block past what a materialised attention matrix allows.
    """
    n = mesh.shape[axis]
    l = q.shape[1]
    if l % n:
        raise ValueError(f"sequence length {l} not divisible by mesh axis {n}")
    block = l // n
    if kv_chunk is not None and (kv_chunk <= 0 or block % kv_chunk):
        raise ValueError(f"kv_chunk {kv_chunk} must divide the per-device "
                         f"block {block}")
    scale = 1.0 / jnp.sqrt(q.shape[-1]).astype(q.dtype)

    def local(qb, kb, vb):
        # qb/kb/vb: [B, block, H, Dh] — this device's shard.
        my = jax.lax.axis_index(axis)          # query-block index
        q_pos = my * block + jnp.arange(block)  # global query positions

        def step(carry, t):
            kv, num, den, m = carry
            kb_t, vb_t = kv
            kv_idx = (my + t) % n               # which key block we hold now
            if kv_chunk is not None:
                num2, den2, m2 = _block_attn_chunked(
                    qb, kb_t, vb_t, scale=scale,
                    q_pos=q_pos if causal else None,
                    k_pos0=kv_idx * block, chunk=kv_chunk)
            else:
                if causal:
                    k_pos = kv_idx * block + jnp.arange(block)
                    mask = q_pos[:, None] >= k_pos[None, :]  # [block, block]
                    mask = mask[None, :, None, :]            # [1, Lq, 1, Lk]
                else:
                    mask = None
                num2, den2, m2 = _block_attn_remat(qb, kb_t, vb_t, scale,
                                                   mask)
            num, den, m = _combine(num, den, m, num2, den2, m2)

            # Rotate KV to the next device — except after the last
            # block, whose rotation would be discarded with the carry
            # (saves one redundant KV-pair hop per call).
            def rotate(kv):
                perm = [((d + 1) % n, d) for d in range(n)]
                return (jax.lax.ppermute(kv[0], axis, perm),
                        jax.lax.ppermute(kv[1], axis, perm))

            kb_n, vb_n = jax.lax.cond(t < n - 1, rotate,
                                      lambda kv: kv, (kb_t, vb_t))
            return ((kb_n, vb_n), num, den, m), None

        # Derive the accumulators from qb so they carry the same
        # varying-manual-axes type as the scan outputs (shard_map
        # rejects unvarying-constant carries combined with varying
        # results).
        num0 = qb * 0
        den0 = jnp.sum(num0, axis=-1)
        m0 = den0 - jnp.inf
        (_, num, den, m), _ = jax.lax.scan(
            step, ((kb, vb), num0, den0, m0), jnp.arange(n))
        # Fully-masked rows (never happens for causal self-attention,
        # where every query sees at least itself) would have den 0.
        return num / jnp.maximum(den, 1e-30)[..., None]

    spec = P(None, axis, None, None)
    fn = jax.shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec)
    return fn(q, k, v)


def ulysses_attention(q, k, v, mesh: Mesh, *, causal: bool = False,
                      axis: str = SEQ_AXIS):
    """All-to-all sequence parallelism (DeepSpeed-Ulysses pattern).

    Input is sequence-sharded [B, L/D, H, Dh] per device; one
    ``all_to_all`` turns it head-sharded [B, L, H/D, Dh], local exact
    attention runs over the FULL sequence for this device's heads, and
    a second ``all_to_all`` restores sequence sharding.  Requires the
    head count divisible by the mesh axis size.
    """
    n = mesh.shape[axis]
    h = q.shape[2]
    if h % n:
        raise ValueError(f"num heads {h} not divisible by mesh axis {n}")
    if q.shape[1] % n:
        raise ValueError(f"sequence length {q.shape[1]} not divisible by {n}")

    def local(qb, kb, vb):
        def seq_to_heads(x):
            # [B, L/D, H, Dh] -> [B, L, H/D, Dh]
            return jax.lax.all_to_all(x, axis, split_axis=2, concat_axis=1,
                                      tiled=True)

        def heads_to_seq(x):
            return jax.lax.all_to_all(x, axis, split_axis=1, concat_axis=2,
                                      tiled=True)

        qh, kh, vh = seq_to_heads(qb), seq_to_heads(kb), seq_to_heads(vb)
        out = dense_attention(qh, kh, vh, causal=causal)
        return heads_to_seq(out)

    spec = P(None, axis, None, None)
    fn = jax.shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec)
    return fn(q, k, v)


def make_seq_mesh(n_devices: int | None = None) -> Mesh:
    """1-D mesh over the sequence-parallel axis."""
    devs = jax.devices()
    n = len(devs) if n_devices is None else n_devices
    return Mesh(devs[:n], (SEQ_AXIS,))
