"""Model zoo in flax.linen (TPU compute path).

Re-creates the reference's two CNNs with exact parameter-count parity
(``models.py`` in both reference projects — `Model1`: 1,663,370 params
for MNIST/FMNIST, `Model3`: 1,105,098 for CIFAR-10) and adds the models
the benchmark configs need: an MLP, ℓ2-regularised logistic regression
(a9a / ADMM), and a GroupNorm ResNet-18 for the 32-worker CIFAR-10
north-star config.

Faithful-head semantics: the reference ends both CNNs in ``nn.Softmax``
*and* trains with ``CrossEntropyLoss`` (which applies log_softmax
internally) — a double softmax (SURVEY §3.4).  ``faithful=True``
reproduces that: ``__call__`` returns *probabilities* and the loss in
``dopt.models.losses`` applies log_softmax on top, bit-matching the
reference's objective.  ``faithful=False`` returns logits (the
corrected, idiomatic head).

Data layout is NHWC (TPU-native).  The reference flattens NCHW
channel-major before its first Dense layer; parameter-conversion
helpers in ``dopt.engine.oracle`` handle that reordering so torch and
flax foward passes are comparable element-wise.
"""

from __future__ import annotations

from typing import Any, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp


def _head(x: jnp.ndarray, faithful: bool) -> jnp.ndarray:
    """Output head: softmax probabilities in faithful mode (the
    reference's double-softmax objective), logits otherwise."""
    if faithful:
        return jax.nn.softmax(x.astype(jnp.float32), axis=-1)
    return x


@jax.custom_vjp
def _tiled_max(x6: jnp.ndarray) -> jnp.ndarray:
    """max over the window axes (2, 4) of a [b, h2, 2, w2, 2, c] tiling
    with a FIRST-WINNER backward: the gradient goes to the first window
    element attaining the max in (di, dj) row-major order — torch
    ``MaxPool2d``'s tie semantics (its backward routes through the
    argmax index, first occurrence in kernel scan order) — instead of
    jax's equal split across ties.  Ties are NOT measure-zero in
    practice: the faithful Model1 conv has no ReLU, so zero-background
    MNIST pixels produce exact 4-way bias ties in every background
    window (ADVICE r4).

    Undifferentiated calls (every eval path) are this plain reduce.
    Under differentiation the forward makes ONE pass over ``x6`` and
    keeps a one-byte winner code per window as its only residual;
    the backward reads the code and the cotangent and nothing of
    ``x6``'s size (PERF.md §5, §6 PR 26)."""
    return x6.max(axis=(2, 4))


def _window_code(shape6) -> jnp.ndarray:
    """int8 position code ``2·di + dj`` of every element of a
    [b, h2, 2, w2, 2, c] tiling: torch's kernel scan order."""
    return (2 * jax.lax.broadcasted_iota(jnp.int8, shape6, 2)
            + jax.lax.broadcasted_iota(jnp.int8, shape6, 4))


def _first_larger(a, b):
    """Reduction step over (value, code) pairs: the larger value, on
    equal values the smaller code; a NaN wins, as in ``max``."""
    (av, ak), (bv, bk) = a, b
    take_a = (av > bv) | ((av == bv) & (ak < bk)) | (av != av)
    return jnp.where(take_a, av, bv), jnp.where(take_a, ak, bk)


@jax.named_scope("dopt_pool")
def _tiled_max_fwd(x6):
    # (max, winner code): the output and the only residual
    return jax.lax.reduce(
        (x6, _window_code(x6.shape)),
        (jnp.array(-jnp.inf, x6.dtype), jnp.int8(3)),
        _first_larger, (2, 4))


@jax.named_scope("dopt_pool")
def _tiled_max_bwd(code, g):
    b, h2, w2, c = code.shape
    hit = code[:, :, None, :, None, :] == _window_code((b, h2, 2, w2, 2, c))
    return (jnp.where(hit, g[:, :, None, :, None, :],
                      jnp.zeros((), g.dtype)),)


_tiled_max.defvjp(_tiled_max_fwd, _tiled_max_bwd)


def _max_pool_2x2(x: jnp.ndarray) -> jnp.ndarray:
    """2×2 stride-2 max pool via reshape + tiled reduce_max.

    Forward-identical to ``nn.max_pool(x, (2, 2), strides=(2, 2))`` for
    even H/W (the windows are non-overlapping, so the reshape tiles them
    exactly).  Its custom VJP (``_tiled_max``) routes tie gradients to
    the FIRST window element in torch's kernel scan order, bit-matching
    MaxPool2d's backward even on real data with exact ties (e.g.
    zero-background MNIST under the no-ReLU faithful conv) — not jax's
    default equal split — and saves an int8 winner code between the
    passes instead of the conv activations.  What the pool costs in the
    stacked Model1 step is ``pool_ms`` (PERF.md §3, §5).

    Odd spatial dims fall back to ``nn.max_pool`` (which floors), since
    the reshape tiling requires even H/W.
    """
    b, h, w, c = x.shape
    if h % 2 or w % 2:
        return nn.max_pool(x, (2, 2), strides=(2, 2))
    return _tiled_max(x.reshape(b, h // 2, 2, w // 2, 2, c))


class _ReferenceCNN(nn.Module):
    """Shared body of the reference's two CNNs (``models.py`` both
    projects): conv(·→32,k5,SAME) → maxpool2 → conv(32→64,k5,SAME) →
    maxpool2 → Dense(hidden) → ReLU → Dense(num_classes) [→ Softmax].
    They differ only in the first Dense width.

    Faithful quirk: the reference conv stack has NO activations — the
    only ReLU sits between the two Dense layers (models.py:10-21).  Two
    stacked linear convs are a strictly weaker function class, but that
    is the architecture the published numbers used; ``faithful=True``
    reproduces it exactly, ``faithful=False`` adds the conventional
    post-conv ReLUs (and drops the softmax head)."""

    hidden: int = 512
    num_classes: int = 10
    faithful: bool = True
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        x = x.astype(self.dtype)
        x = nn.Conv(32, (5, 5), padding="SAME", dtype=self.dtype, name="conv1")(x)
        if not self.faithful:
            x = nn.relu(x)
        x = _max_pool_2x2(x)
        x = nn.Conv(64, (5, 5), padding="SAME", dtype=self.dtype, name="conv2")(x)
        if not self.faithful:
            x = nn.relu(x)
        x = _max_pool_2x2(x)
        x = x.reshape((x.shape[0], -1))
        x = nn.Dense(self.hidden, dtype=self.dtype, name="fc1")(x)
        x = nn.relu(x)
        # Corrected head: compute the logits layer in f32 (standard
        # mixed-precision practice — the raw-logit objective is
        # sensitive to bf16 rounding of the logit gradients, measured
        # run-to-run final-acc scatter 0.3-0.96 vs a tight band with the
        # f32 head; ~5k MACs/sample, free).  Faithful mode keeps the
        # compute dtype end-to-end: its double-softmax objective is
        # insensitive (softmax squashing) and the oracle parity
        # contract pins its op sequence.
        head_dtype = self.dtype if self.faithful else jnp.float32
        x = nn.Dense(self.num_classes, dtype=head_dtype, name="fc2")(x)
        return _head(x, self.faithful)


class Model1(_ReferenceCNN):
    """MNIST/FMNIST CNN (reference ``models.py:6-27``), 1,663,370 params."""

    hidden: int = 512


class Model3(_ReferenceCNN):
    """CIFAR CNN (reference ``models.py:31-51``), 1,105,098 params @ 10 classes."""

    hidden: int = 256


class MLP(nn.Module):
    """Small MLP (BASELINE.json config 1: 4-worker MNIST MLP)."""

    hidden: Sequence[int] = (200, 200)
    num_classes: int = 10
    faithful: bool = False
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        x = x.astype(self.dtype).reshape((x.shape[0], -1))
        for i, h in enumerate(self.hidden):
            x = nn.relu(nn.Dense(h, dtype=self.dtype, name=f"fc{i+1}")(x))
        x = nn.Dense(self.num_classes, dtype=self.dtype, name="head")(x)
        return _head(x, self.faithful)


class LogisticRegression(nn.Module):
    """ℓ2-regularised logistic regression (BASELINE.json config 4:
    16-worker ADMM on a9a).  The ℓ2 term lives in the loss, not here."""

    num_classes: int = 2
    faithful: bool = False
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        x = x.astype(self.dtype).reshape((x.shape[0], -1))
        x = nn.Dense(self.num_classes, dtype=self.dtype, name="linear")(x)
        return _head(x, self.faithful)


class ResidualBlock(nn.Module):
    features: int
    strides: int = 1
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        residual = x
        y = nn.Conv(self.features, (3, 3), strides=(self.strides, self.strides),
                    padding="SAME", use_bias=False, dtype=self.dtype)(x)
        y = nn.GroupNorm(num_groups=min(32, self.features))(y)
        y = nn.relu(y)
        y = nn.Conv(self.features, (3, 3), padding="SAME", use_bias=False,
                    dtype=self.dtype)(y)
        y = nn.GroupNorm(num_groups=min(32, self.features))(y)
        if residual.shape != y.shape:
            residual = nn.Conv(self.features, (1, 1),
                               strides=(self.strides, self.strides),
                               use_bias=False, dtype=self.dtype)(residual)
            residual = nn.GroupNorm(num_groups=min(32, self.features))(residual)
        return nn.relu(y + residual)


class ResNet18(nn.Module):
    """CIFAR-style ResNet-18 with GroupNorm (BASELINE.json config 5:
    32-worker gossip SGD, CIFAR-10, time-varying random graphs).

    GroupNorm instead of BatchNorm: batch statistics are ill-defined
    under federated/gossip averaging (each worker's running stats
    diverge and averaging them is not principled), and GN keeps the
    model a pure function of (params, batch) — no mutable state to
    thread through the stacked-worker engine.  Standard choice in the
    FL literature.
    """

    num_classes: int = 10
    faithful: bool = False
    dtype: Any = jnp.float32
    stage_sizes: Sequence[int] = (2, 2, 2, 2)

    @nn.compact
    def __call__(self, x):
        x = x.astype(self.dtype)
        x = nn.Conv(64, (3, 3), padding="SAME", use_bias=False, dtype=self.dtype)(x)
        x = nn.GroupNorm(num_groups=32)(x)
        x = nn.relu(x)
        for stage, blocks in enumerate(self.stage_sizes):
            features = 64 * (2 ** stage)
            for b in range(blocks):
                strides = 2 if (stage > 0 and b == 0) else 1
                x = ResidualBlock(features, strides=strides, dtype=self.dtype)(x)
        x = jnp.mean(x, axis=(1, 2))
        x = nn.Dense(self.num_classes, dtype=self.dtype, name="head")(x)
        return _head(x, self.faithful)


class TransformerLM(nn.Module):
    """Decoder-only transformer LM — the long-context member of the zoo.

    Nothing like it exists in the reference (no attention, no sequence
    axis anywhere — SURVEY §2.3); this is the framework's own
    demonstration that its sequence-parallel substrate
    (``dopt.parallel.sequence``) plugs into a real model.  ``attn_fn``
    injects the attention implementation: ``None`` uses single-device
    dense attention; pass ``lambda q,k,v: ring_attention(q,k,v,mesh,
    causal=True)`` (or the Ulysses variant) to shard the sequence axis
    over a mesh with NO other change to the model.

    Pre-LN blocks, learned positional embeddings, weight-tied output
    head.  Call input: [B, L] int32 tokens; output [B, L, vocab]
    logits (``num_classes`` is the vocab size).
    """

    num_classes: int = 256          # vocab
    faithful: bool = False          # kept for zoo-interface uniformity
    dtype: Any = jnp.float32
    dim: int = 128
    depth: int = 2
    heads: int = 4
    max_len: int = 2048

    @nn.compact
    def __call__(self, tokens, attn_fn=None):
        from dopt.parallel.sequence import dense_attention

        attn = attn_fn or (lambda q, k, v: dense_attention(q, k, v,
                                                           causal=True))
        b, l = tokens.shape
        if l > self.max_len:
            raise ValueError(f"sequence length {l} > max_len {self.max_len}")
        if self.dim % self.heads:
            raise ValueError(f"dim {self.dim} not divisible by "
                             f"heads {self.heads}")
        emb = nn.Embed(self.num_classes, self.dim, dtype=self.dtype,
                       name="tok_emb")
        x = emb(tokens)
        x = x + self.param(
            "pos_emb", nn.initializers.normal(0.02),
            (self.max_len, self.dim))[None, :l].astype(self.dtype)
        hd = self.dim // self.heads
        for i in range(self.depth):
            y = nn.LayerNorm(dtype=self.dtype, name=f"ln1_{i}")(x)
            qkv = nn.Dense(3 * self.dim, use_bias=False, dtype=self.dtype,
                           name=f"qkv_{i}")(y)
            q, k, v = jnp.split(qkv.reshape(b, l, 3 * self.heads, hd), 3,
                                axis=2)
            o = attn(q, k, v).reshape(b, l, self.dim)
            x = x + nn.Dense(self.dim, use_bias=False, dtype=self.dtype,
                             name=f"proj_{i}")(o)
            y = nn.LayerNorm(dtype=self.dtype, name=f"ln2_{i}")(x)
            y = nn.Dense(4 * self.dim, dtype=self.dtype, name=f"up_{i}")(y)
            y = nn.gelu(y)
            x = x + nn.Dense(self.dim, dtype=self.dtype, name=f"down_{i}")(y)
        x = nn.LayerNorm(dtype=self.dtype, name="ln_f")(x)
        logits = x @ emb.embedding.T.astype(self.dtype)
        return _head(logits, self.faithful)


def _to_grouped_kernel(k):
    """[W, kh, kw, Cin, Cout] stacked conv kernel → the grouped-conv
    layout [kh, kw, Cin, W·Cout] (worker-major output channels).  A
    pure permutation — bit-exactly invertible."""
    g = jnp.moveaxis(k, 0, 3)
    return g.reshape(*g.shape[:3], -1)


def _conv_fast(z, g_kernel, groups, *, dtype, strides=(1, 1),
               padding="SAME", bias=None, precision=None):
    """Worker-grouped conv on [B, H, Wd, G·Cin] with a pre-grouped
    [kh, kw, Cin, G·Cout] kernel (``_to_grouped_kernel`` layout)."""
    out = jax.lax.conv_general_dilated(
        z, g_kernel.astype(dtype), strides, padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups, precision=precision)
    if bias is not None:
        out = out + bias.astype(dtype).reshape(1, 1, 1, -1)
    return out


# Lanes of a vector register, and columns of an MXU tile, on the chips
# this runs on (v5e: 128).
_LANES = 128

# Stacked width from which conv1 packs at a batch that is NOT whole lane
# tiles.  An empirical crossover with no lane mechanism behind it: conv1
# + bias + pool on the v5e, grouped ÷ packed time (over 1: packing
# wins; ``scripts/conv1_bench.py``, PERF.md §6 PR 31; "fwd" = forward
# alone, else forward and gradient).  At W = 160 every point wins:
# B 37 2.02 (fwd 1.98: the ring's holdout eval), 50 1.20 (fwd 1.50),
# 100 1.10 (fwd 1.60).  At W = 128 they are mixed: B 50 0.98 (fwd 1.21),
# 100 0.95 (fwd 0.93), 37 fwd 1.71.  Below, packing loses: B = 50:
# W 8 0.60 (FedAvg's step), 16 0.65, 32 0.65, 64 0.70; B = 100: W 8 0.85,
# 12 0.50, 32 0.60.
_CONV1_PACK_WIDTH = 160


def _conv1_stacked(z, g_kernel, groups, *, dtype, bias):
    """The reference CNNs' first convolution over the stacked fleet:
    ``_conv_fast`` with bias, under the ``dopt_conv1`` scope
    (``conv1_ms``, PERF.md §3).

    With ONE input channel a worker, ``feature_group_count = W`` is a
    depthwise convolution with a channel multiplier, which XLA:TPU
    gives to its vector-unit emitter: 1.4 TFLOP/s and an output in a
    layout that a 2 GB copy then turns for the pool (PERF.md §5, §6
    PR 31).  So ``p = 128 // C_out`` consecutive workers share a group
    (4 at 32 output channels: one MXU tile of output lanes): the input
    is unchanged, worker-major channels put pack-mates side by side
    already; the kernel becomes [kh, kw, p, W·C_out], worker b's taps
    on input channel ``b % p`` of its group and zero on its pack-mates'
    channels.  The MXU emitter takes that form and writes the layout
    the pool reads.  The same sums plus exact zeros: the result differs
    from the grouped form's by float reassociation only.

    The zero blocks are SELECTED (``jnp.where`` on a constant mask),
    never multiplied in: a worker whose kernel went non-finite must not
    reach its pack-mates (``NaN · 0 = NaN``), and the selection's
    gradient keeps the diagonal blocks only.

    The pixels stay float32.  The vector-unit emitter multiplied
    float32 pixels by a kernel (forward) or a cotangent (weight
    gradient) at the ambient precision; the MXU at the ambient default
    would round the pixels to bfloat16 as well.  ``precision=(HIGHEST,
    ambient)`` keeps the grouped form's operands (three MXU passes for
    one: 27 ms a round on the ring, where packing gains 315; PERF.md §6
    PR 31), and jax's transpose rule hands the same pair to the weight
    gradient, whose first operand is again the pixels.

    The rule reads what the call can see.  Whether it CAN pack: one
    input channel a worker and a fleet that packs evenly; anything else
    (Model3's three channels, a fleet of 6, the single global model) is
    the grouped form, bit for bit.  Whether it PAYS: with a batch of
    whole lane tiles (B % 128 == 0) the packed output is written
    batch-minor, as the pool reads it, and packing wins at every width
    measured, 1.3–3.7× from W = 4 to 160; at any other batch relayouts
    follow the packed convolution, which only a fleet of
    ``_CONV1_PACK_WIDTH`` or more outweighs (there the grouped form's
    vector-unit time has grown past them).  Padding such a batch to
    whole tiles instead was measured and rejected: FedAvg's 8 × 50
    step +31 ms a round against the grouped form (PERF.md §6 PR 31).
    """
    c_out = g_kernel.shape[-1] // groups
    p = _LANES // c_out
    packs = g_kernel.shape[2] == 1 and p > 1 and groups % p == 0
    pays = z.shape[0] % _LANES == 0 or groups >= _CONV1_PACK_WIDTH
    with jax.named_scope("dopt_conv1"):
        if not (packs and pays):
            return _conv_fast(z, g_kernel, groups, dtype=dtype, bias=bias)
        ambient = jax.config.jax_default_matmul_precision
        return _conv_fast(
            z, _pack_kernel(g_kernel, c_out, p), groups // p, dtype=dtype,
            bias=bias, precision=(jax.lax.Precision.HIGHEST,
                                  jax.lax.Precision(ambient or "default")))


def _pack_kernel(g_kernel, c_out, p):
    """[kh, kw, 1, W·C_out] grouped kernel → [kh, kw, p, W·C_out]: worker
    b's taps on input channel ``b % p``, selected zeros on the rest."""
    mate = (jnp.arange(g_kernel.shape[-1]) // c_out) % p    # [W·C_out]
    mine = jnp.arange(p)[:, None] == mate[None, :]          # [p, W·C_out]
    return jnp.where(mine, g_kernel, jnp.zeros((), g_kernel.dtype))


def _group_norm_stacked(z, scale, bias, *, num_workers, groups_per_worker,
                        eps=1e-6):
    """flax ``GroupNorm`` over worker-major stacked channels.

    z is [B, H, Wd, W·C]; with worker-major channel packing the W·g
    stacked groups tile exactly into per-worker channel blocks, so each
    group's statistics are computed within one worker — identical math
    to vmapping GroupNorm(num_groups=g) per worker.

    Statistics use float32 ACCUMULATION (``jnp.mean(..., dtype=f32)``
    with flax's E[x²]−E[x]² formula) but the big activation tensor is
    never materialised in f32: the normalisation collapses to one fused
    ``z·a + c`` in the compute dtype with per-(sample, channel) f32
    coefficients — an explicit f32 upcast of the activations here cost
    41% of baseline5's device time as convert_element_type ops.
    """
    b, h, wd, wc = z.shape
    g = num_workers * groups_per_worker
    cpg = wc // g
    zg = z.reshape(b, h, wd, g, cpg)
    mean = jnp.mean(zg, axis=(1, 2, 4), dtype=jnp.float32)          # [b, g]
    mean2 = jnp.mean(jnp.square(zg), axis=(1, 2, 4), dtype=jnp.float32)
    var = jnp.maximum(mean2 - jnp.square(mean), 0.0)
    inv = jax.lax.rsqrt(var + eps)                                   # [b, g]
    inv_c = jnp.broadcast_to(inv[:, :, None], (b, g, cpg)).reshape(b, wc)
    mean_c = jnp.broadcast_to(mean[:, :, None], (b, g, cpg)).reshape(b, wc)
    sc = scale.reshape(wc).astype(jnp.float32)[None]
    bi = bias.reshape(wc).astype(jnp.float32)[None]
    a = (sc * inv_c).astype(z.dtype)
    c0 = (bi - mean_c * inv_c * sc).astype(z.dtype)
    return z * a[:, None, None, :] + c0[:, None, None, :]


def _map_named_kernels(tree, ndim, fn):
    """Recursively apply ``fn`` to every dict value under key 'kernel'
    whose rank is ``ndim``; everything else passes through."""
    if isinstance(tree, dict):
        return {k: (fn(v) if k == "kernel" and getattr(v, "ndim", 0) == ndim
                    else _map_named_kernels(v, ndim, fn))
                for k, v in tree.items()}
    return tree


def _make_stacked_resnet_apply(model: "ResNet18"):
    """Grouped-stacked forward for the GroupNorm ResNet-18 (the
    north-star config's model): every conv becomes a
    feature_group_count=W conv over worker-major channels, GroupNorm
    becomes W·32 stacked groups, and the head a batched einsum.

    The conv kernels are permuted into the grouped layout
    (``_to_grouped_kernel``) at the top of each apply; hoisting that
    relayout out of the step by CARRYING grouped-layout params through
    the scan was measured and rejected — XLA then picks worse layouts
    for the carried kernels (headline 378→401 ms/round, baseline5
    2410→2572 ms/round device time).
    """
    dtype, faithful = model.dtype, model.faithful
    stage_sizes = tuple(model.stage_sizes)

    def apply(params, x):
        fp = _map_named_kernels(params, 5, _to_grouped_kernel)
        w, b = x.shape[0], x.shape[1]
        z = jnp.moveaxis(x.astype(dtype), 0, 3)
        z = z.reshape(*z.shape[:3], -1)
        z = _conv_fast(z, fp["Conv_0"]["kernel"], w, dtype=dtype)
        gn = fp["GroupNorm_0"]
        z = _group_norm_stacked(z, gn["scale"], gn["bias"], num_workers=w,
                                groups_per_worker=32)
        z = nn.relu(z)
        blk = 0
        for stage, blocks in enumerate(stage_sizes):
            for bi in range(blocks):
                strides = 2 if (stage > 0 and bi == 0) else 1
                bp = fp[f"ResidualBlock_{blk}"]
                blk += 1
                gpw = min(32, bp["Conv_0"]["kernel"].shape[-1] // w)
                residual = z
                y = _conv_fast(z, bp["Conv_0"]["kernel"], w, dtype=dtype,
                               strides=(strides, strides))
                y = _group_norm_stacked(
                    y, bp["GroupNorm_0"]["scale"], bp["GroupNorm_0"]["bias"],
                    num_workers=w, groups_per_worker=gpw)
                y = nn.relu(y)
                y = _conv_fast(y, bp["Conv_1"]["kernel"], w, dtype=dtype)
                y = _group_norm_stacked(
                    y, bp["GroupNorm_1"]["scale"], bp["GroupNorm_1"]["bias"],
                    num_workers=w, groups_per_worker=gpw)
                if "Conv_2" in bp:
                    residual = _conv_fast(
                        residual, bp["Conv_2"]["kernel"], w, dtype=dtype,
                        strides=(strides, strides))
                    residual = _group_norm_stacked(
                        residual, bp["GroupNorm_2"]["scale"],
                        bp["GroupNorm_2"]["bias"], num_workers=w,
                        groups_per_worker=gpw)
                z = nn.relu(y + residual)
        z = jnp.mean(z, axis=(1, 2))                 # [B, W·C]
        z = z.reshape(b, w, -1)
        hd = fp["head"]
        z = (jnp.einsum("bwi,wio->bwo", z, hd["kernel"].astype(dtype))
             + hd["bias"].astype(dtype)[None])
        z = jnp.moveaxis(z, 1, 0)                    # [W, B, ncls]
        return _head(z, faithful)

    return apply


def _make_stacked_cnn_apply(model: "_ReferenceCNN"):
    """Grouped-stacked forward for the reference CNNs.

    The conv kernels are permuted to the grouped layout AND the FC
    kernels reshaped to their VALID-conv form at the top of each apply
    — a Dense over the flattened [H', Wd', C2] is exactly an H'×Wd'
    VALID conv, and keeping the worker axis in channels end-to-end
    avoids a [W·B·3136] activation relayout between conv and FC whose
    forward+backward transposes cost ~2× the conv time in the einsum
    formulation (measured on v5e).  flax flattens [H', Wd', C2]
    row-major, so the [W, H'·Wd'·C2, O] kernel reshapes to
    [W, H', Wd', C2, O] with matching index order.  (Carrying the
    grouped layout through the training scan instead was measured and
    rejected — see ``_make_stacked_resnet_apply``.)

    conv1 alone does not run one group a worker where it can pack:
    with one input channel a worker that form is a depthwise
    convolution, which the chip runs on its vector unit; four workers a
    group run on the MXU (``_conv1_stacked``; PERF.md §5, §6 PR 31).
    """
    faithful, dtype = model.faithful, model.dtype

    def to_fast(p, hp, wp):
        """hp/wp: the post-pool spatial dims, taken from the ACTUAL
        activation shape at the fc1 call site (not inferred by a square
        root — non-square inputs reshape correctly, ADVICE r4)."""
        c2n = p["conv2"]["kernel"].shape[-1]
        f1 = p["fc1"]["kernel"]           # [W, H'·Wd'·C2, hidden]
        if f1.shape[1] != hp * wp * c2n:
            raise ValueError(
                f"fc1 kernel fan-in {f1.shape[1]} != post-pool "
                f"H'·Wd'·C2 = {hp}·{wp}·{c2n}")
        f2 = p["fc2"]["kernel"]           # [W, hidden, ncls]
        return {
            "conv1": {"kernel": _to_grouped_kernel(p["conv1"]["kernel"]),
                      "bias": p["conv1"]["bias"]},
            "conv2": {"kernel": _to_grouped_kernel(p["conv2"]["kernel"]),
                      "bias": p["conv2"]["bias"]},
            "fc1": {"kernel": _to_grouped_kernel(
                        f1.reshape(f1.shape[0], hp, wp, c2n, f1.shape[2])),
                    "bias": p["fc1"]["bias"]},
            "fc2": {"kernel": _to_grouped_kernel(
                        f2.reshape(f2.shape[0], 1, 1, *f2.shape[1:])),
                    "bias": p["fc2"]["bias"]},
        }

    def apply(params, x):
        w, b = x.shape[0], x.shape[1]
        h_in, w_in = x.shape[2], x.shape[3]
        # Post-pool spatial dims after two stride-2 pools (floored —
        # nn.max_pool's odd-dim behaviour).
        hp, wp = h_in // 2 // 2, w_in // 2 // 2
        fp = to_fast(params, hp, wp)
        # [W, B, H, Wd, C] → [B, H, Wd, W·C] (worker-major channels)
        z = jnp.moveaxis(x.astype(dtype), 0, 3)
        z = z.reshape(*z.shape[:3], -1)
        z = _conv1_stacked(z, fp["conv1"]["kernel"], w, dtype=dtype,
                           bias=fp["conv1"]["bias"])
        if not faithful:
            z = nn.relu(z)
        z = _max_pool_2x2(z)
        z = _conv_fast(z, fp["conv2"]["kernel"], w, dtype=dtype,
                       bias=fp["conv2"]["bias"])
        if not faithful:
            z = nn.relu(z)
        z = _max_pool_2x2(z)          # [B, H', Wd', W·C2]
        z = _conv_fast(z, fp["fc1"]["kernel"], w, dtype=dtype,
                       padding="VALID", bias=fp["fc1"]["bias"])
        z = nn.relu(z)
        # f32 logits layer on the corrected head — mirrors the flax
        # module (see _ReferenceCNN.__call__).
        head_dtype = dtype if faithful else jnp.float32
        z = _conv_fast(z.astype(head_dtype), fp["fc2"]["kernel"], w,
                       dtype=head_dtype, padding="VALID",
                       bias=fp["fc2"]["bias"])
        ncls = z.shape[-1] // w
        z = z.reshape(b, w, ncls)
        z = jnp.moveaxis(z, 1, 0)                 # [W, B, ncls]
        return _head(z, faithful)

    return apply


def resolve_stacked_apply(model, stacked_impl: str):
    """Validate ``ModelConfig.stacked_impl`` and resolve the grouped
    stacked forward for it — the one shared entry point both engines
    use, so the accepted values can never drift between them."""
    if stacked_impl not in ("auto", "vmap"):
        raise ValueError(
            f"unknown stacked_impl {stacked_impl!r}; one of auto|vmap")
    return make_stacked_apply(model) if stacked_impl == "auto" else None


def make_stacked_apply(model) -> "callable | None":
    """Stacked-worker forward for the reference CNNs and the ResNet as
    ONE grouped-conv program — the engine's fast path around
    ``vmap(model.apply)``.

    XLA lowers a conv vmapped over per-worker kernels poorly on TPU
    (layout shuffles around every conv; measured 1.6× step slowdown at
    6 workers and ~4× at 32).  The same math maps exactly onto a single
    ``conv_general_dilated`` with ``feature_group_count=W``: put the
    worker axis into the channel dimension ([W, B, H, Wd, C] →
    [B, H, Wd, W·C]) and concatenate the per-worker kernels into
    [kh, kw, C, W·Cout] — group w then convolves worker w's channels
    with worker w's kernel, which is precisely the stacked-fleet
    forward.  Prototype measurement: 0.43 ms vs 1.43 ms per fused train
    step on the headline workload (v5e).  The one exception is the
    reference CNNs' first convolution, whose single input channel a
    worker would make that a depthwise convolution on the vector unit:
    it packs four workers a group with selected zero blocks, so that
    the MXU takes it (``_conv1_stacked``; PERF.md §6 PR 31).

    Returns ``apply(stacked_params, x)`` mapping a [W, ...]-stacked
    param pytree (the engine's native layout) and [W, B, H, Wd, C]
    inputs to [W, B, num_classes] outputs — bit-comparable to
    ``vmap(model.apply)`` up to float reassociation inside the conv —
    or ``None`` for models without a grouped-stacked form (the engines
    fall back to vmap).
    """
    if isinstance(model, ResNet18):
        return _make_stacked_resnet_apply(model)
    if isinstance(model, _ReferenceCNN):
        return _make_stacked_cnn_apply(model)
    return None


_ZOO = {
    "model1": Model1,
    "model3": Model3,
    "mlp": MLP,
    "logistic": LogisticRegression,
    "resnet18": ResNet18,
    "transformer": TransformerLM,
}


def build_model(
    name: str,
    *,
    num_classes: int = 10,
    faithful: bool | None = None,
    dtype: Any = jnp.float32,
    stage_sizes: Sequence[int] | None = None,
    decoder=None,
) -> nn.Module:
    """Model dispatch by name — the typed replacement for the reference's
    if/elif on ``args.model`` (``servers.py:33-40``, ``simulators.py:31-38``).

    ``faithful=None`` keeps each model's own default: True only for
    the two reference CNNs (which have a double-softmax to be faithful
    to), False for mlp/logistic/resnet18 (new models, corrected head).
    ``dtype`` may be a string ("bfloat16" → MXU-native compute); params
    stay float32 (flax param_dtype default) — bf16 is compute-only.
    ``stage_sizes`` (resnet18 only) overrides the per-stage block counts
    for shallow variants.  ``decoder`` (model ``decoder``, or ``laguna``
    as the first one was named) is the ``DecoderConfig``, whose
    ``model_type`` says which layer is built; ``num_classes`` is then
    the vocabulary rows held.
    """
    if isinstance(dtype, str):
        dtype = jnp.dtype(dtype)
    key = name.lower()
    if key in ("decoder", "laguna"):     # "laguna": the first one's name
        from dopt.models.decoder import GatedMoEDecoder

        if decoder is None:
            raise ValueError(f"model={key!r} needs ModelConfig.decoder "
                             "(dopt.config.DecoderConfig)")
        return GatedMoEDecoder(decoder, vocab_rows=num_classes, dtype=dtype)
    if decoder is not None:
        raise ValueError("ModelConfig.decoder applies to model='decoder' only")
    if key not in _ZOO:
        raise ValueError(
            f"unknown model {name!r}; one of {sorted([*_ZOO, 'decoder'])}")
    kwargs: dict[str, Any] = dict(num_classes=num_classes, dtype=dtype)
    if faithful is not None:
        kwargs["faithful"] = faithful
    if stage_sizes is not None:
        if key != "resnet18":
            raise ValueError("stage_sizes applies to resnet18 only")
        kwargs["stage_sizes"] = tuple(stage_sizes)
    return _ZOO[key](**kwargs)


def count_params(params) -> int:
    return sum(p.size for p in jax.tree_util.tree_leaves(params))
