"""A mixture-of-experts decoder as ONE worker of the gossip engine
(``model="decoder"``, configured by ``dopt.config.DecoderConfig`` under
the keys of the published ``config.json``, whose ``model_type`` says
which layer is built): token ids in, next-token loss out.  Four layers
are known: ``laguna``'s gated window/full attention beside
sigmoid-routed experts and a shared one, ``KeyeVL2``'s learned sparse
attention (a lightning indexer picks the ``topk`` keys a query attends
and an alignment term trains it) beside softmax-routed experts,
``joyai_llm_flash``'s multi-head latent attention beside sigmoid-routed
experts chosen by score plus a correction bias and a shared one, with a
multi-token-prediction module in the loss, and ``smallthinker``'s full
attention without rotary (NoPE) or banded attention with it, by layer,
beside ReGLU experts whose softmax router reads the layer's input before
the attention.

Not a flax module: the parameters are a plain dict tree (``embed``,
``layer<i>`` with ``attn_norm q k v o mlp_norm``, ``gate`` or ``q_norm
k_norm indexer`` or neither -- or, latent, ``attn_norm q_a q_a_norm q_b kv_a
kv_a_norm kv_b o mlp_norm`` --, and ``mlp`` or ``router experts`` with
or without ``router_bias`` and ``shared``; ``mtp`` with ``enorm hnorm
eh_proj layer norm`` where the model has the module; ``norm``,
``head``; the experts' leaves carry a leading EXPERT axis) and the surface the engines use is ``init(key, dummy)``,
``apply({"params": p}, tokens)`` and ``loss(p, tokens, labels,
weights)``.  The worker axis is the engines' ``vmap`` over the stacked
fleet state.

What a layer computes is written out in the docstrings of
``benchmark/reference_models/laguna_xs2.py``, ``keye_vl2.py``,
``joyai_llm_flash.py`` and ``smallthinker_21b.py``; this file is the
same mathematics arranged for the chip:

* matmul inputs in the compute dtype (bfloat16 on the chip) with float32
  accumulation; residual stream, norms, router scores, rotary,
  attention softmax and the loss in float32; where a layer's queries and
  keys are projections' products with no per-head norm between, rotary,
  the queries' scale and the cast to the compute dtype are one pass a
  tensor of this repo's own Pallas kernels, ``dopt.ops.rotary``,
  wherever their shape limits allow (``rotary_path``), and ``_rotary``
  elsewhere;
* attention a block of ``ATTN_BLOCK`` queries at a time against the keys
  that block can see — everything up to its end in a full layer, the
  band of ``sliding_window`` in front of it in a sliding one — so a
  banded layer never forms T x T (``causal_attention``: one entry, and
  the one place that says which of its two bodies runs and why); where
  the layer has an indexer the mask comes from the data and a third
  body takes it (``indexed_causal_attention``: index scores, selection,
  masked softmax, values and the alignment term a block of queries at a
  time, so no T x T array is held for a row; the index scores and what
  follows the selection run as this repo's own Pallas kernels,
  ``dopt.ops.sparse_attention``, wherever their shape limits allow,
  ``indexed_attention_path``, and in ``jax.numpy`` elsewhere: with the
  kernels neither the indexer's [heads, queries, keys] products nor the
  attention's scores ever leave VMEM, forward or backward);
* an expert layer that is TOLD which experts it holds
  (``expert_offset``, ``experts_held``), routes every token over all
  ``num_experts`` published ones and adds its own experts' part beside
  the shared expert.  Dispatch is DROPLESS and sparse: the routed
  (token, held expert) slots are sorted by expert and only they are
  multiplied, as a grouped matmul whose group sizes are data
  (``dopt.ops.grouped_experts``: three Pallas kernels that gather a
  tile's token rows, multiply them with their expert's matrices and add
  the weighted result back onto the tokens, wherever their shape limits
  allow, ``expert_path``; the same sorted slots in ``jax.numpy``
  elsewhere).  No capacity and no dropped slot: the result is exact for
  ANY routing, from no token at all to every token choosing every held
  expert, at a cost that follows the slots (``num_experts_per_tok * held
  / num_experts`` expert passes a token in expectation);
* the output head and the loss a block of ``HEAD_BLOCK`` positions at a
  time;
* a layer, an attention block and a head block are ``jax.checkpoint``-ed.
  A layer keeps its matmul products (``LAYER_KEEPS``): the fused attention
  kernel's output, q / k / v as the attention takes them, the per-head
  gate's and the router's products, the residual stream after the output
  projection, every gated MLP's gate and up products (the held experts'
  over their routed slots) and the router's chosen experts, so its
  backward pass recomputes elementwise work, and no matmul and no top-k.
  The layers of one kind (``GatedMoEDecoder.layer_kinds``: query heads,
  rotary, window, MLP) share ONE ``jax.jit`` of that checkpoint, so a
  program traces, differentiates, batches and lowers each kind once, not
  each layer; XLA inlines the calls.

Scopes (inside the engines' ``dopt_local``): ``dopt_attn`` (normed input
to gated output projection; a compiled kernel may carry no name stack
and goes by its own name: ``splash_mqa_*``, and
``dopt_attn_dopt_attend_fwd`` / ``_probs`` / ``_bwd``,
``dopt_attn_dopt_index_fwd`` / ``_bwd`` and ``dopt_attn_dopt_rope_fwd``
/ ``_bwd``, which spell out the two scopes they stand in) with, inside
it, ``dopt_qkv`` (the q / k / v projections and the per-head gate's; in
a latent layer the two up-projections, and ``dopt_latent`` inside it:
the two down-projections and their norms), ``dopt_rope`` (what lies
between them and the attention: per-head norms, rotary, the queries'
scale for the kernel, the casts to the compute dtype; where
``rotary_path`` takes the rotary kernels, those and the small table and
slice passes XLA leaves around them), ``dopt_splash`` (on the kernel's path,
the kernel's call: what XLA does around it to its operands and
outputs), ``dopt_oproj``
(gate, output projection; where the layer has an indexer, a run of
blocks at a time) -- the layer-input norm and the residual add stay
under ``dopt_attn`` alone -- and, where the layer has an
indexer, ``dopt_index`` (indexer projections, index scores --
the two index kernels or the ``jax.numpy`` definition --, selection,
alignment term) with ``dopt_select`` inside that (the k-th
largest score and the mask, alone) and ``dopt_attend`` (masked scores,
softmax, value product, the head-mean: the three kernels and the little
that feeds them, or the ``jax.numpy`` body); ``dopt_moe`` (router to combined
output; the held experts' kernels go by their own names,
``dopt_moe_experts_fwd`` / ``_dx`` / ``_dw``) with ``dopt_route`` inside
it (scores, top-k, combine weights, and the dispatch: where each routed
slot sits among the sorted slots and the combine weights' gradient, not
the expert matmuls; where the router reads the layer's input, its
scores, softmax, top-k, combine weights and hits run before the
attention, under ``dopt_preroute`` inside ``dopt_route``, and the slots'
layout stays with the experts' call) and ``dopt_shared`` (the shared
expert's MLP);
``dopt_mlp`` (a dense layer's MLP, without its norm and residual add);
``dopt_head`` (final norm, logits, loss); ``dopt_mtp`` (the
multi-token-prediction module: its norms and projection, its layer, whose
scopes nest inside, and its pass through the head under ``dopt_head``).
``loss`` also returns the step's counts (``model.counters``: routing,
the indexer's two, the module's loss and accuracy, ReGLU's share of
zeros), which the gossip engine averages into each round's history
row.
"""

from __future__ import annotations

import collections
import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from dopt.config import DecoderConfig
from dopt.ops import grouped_experts, sparse_attention
from dopt.ops import rotary as rotary_kernels
from dopt.ops.grouped_experts import running_count as _running_count

# What a layer's ``jax.checkpoint`` keeps of its forward pass
# (``LAYER_KEEPS``).  ``ATTN_RESIDUALS``: the fused attention kernel's
# output and log-sum-exp.  ``MATMUL_PRODUCTS``: every value whose
# recompute would be a matmul, in the dtype the forward pass already
# holds it in: q (rotated, cast and, for the kernel, scaled), k and v as
# the attention takes them, the per-head gate's and the router's
# products before their sigmoids, the float32 residual stream after the
# output projection, and the gate and up products of the dense MLP, the
# shared expert and the held experts (these over the routed slots, with
# the slots' layout: ``dopt.ops.grouped_experts``), and the experts the
# router's ``top_k`` chose (the values are taken at them).  A tag sits
# on the product itself, not past an activation: the backward pass of
# ``sigmoid`` asks for that primitive's own output, which no name
# reaches.  At the
# benchmark's cell that is 181-236 MB a layer, worker and row beside the
# kernel's 51-68 (1.01 GB over the five layers, PERF.md, PR 29), and it
# grows with rows x positions x layers a worker.  The recompute keeps
# norms, casts, activations, the gated attention output and the combine
# weights.  A layer with an indexer keeps q and k
# as float32 PRODUCTS, before their per-head norms (whose backward asks
# for them; norm, rotary and cast are recomputed), the indexer's three
# products likewise, and under ``ATTN_RESIDUALS`` the indexed
# attention's output, so that the blocks run forward once for the
# layer and once inside their own checkpoints, not a third time; where
# the blocks run the fused kernels, also their log-sum-exp, and the
# recompute inside a block's checkpoint is then the index scores, the
# selection and the head-mean, not the attention.  A latent layer keeps
# its two down-projections' float32 products (their norms' backward asks
# for them; the rotary key is a slice of one) and q, k, v as the
# attention takes them; the up-projections' products feed only linear
# passes and are not needed.
ATTN_RESIDUALS = "attn_residuals"
MATMUL_PRODUCTS = "matmul_products"
LAYER_KEEPS = (ATTN_RESIDUALS, MATMUL_PRODUCTS)
# The routing counts ``loss`` returns beside "acc", in history-row order,
# and those of a layer with an indexer: its alignment term (mean over
# layers: does the indexer follow the attention?) and selected over
# visible keys (0.4375 at 8,192 positions and 2,048 keys a query).
COUNTERS = ("moe_held_slot_share", "moe_load_max_over_mean")
INDEX_COUNTERS = ("index_align_loss", "index_keys_kept_share")
# A model with a multi-token-prediction module: its cross-entropy against
# the token after next and its accuracy there.
MTP_COUNTERS = ("mtp_loss", "mtp_acc")
# A model with ReGLU experts: the share of relu(x W_gate) entries that are
# 0 over the routed slots (mean over layers): how sparse the experts'
# activations are.
ACT_COUNTERS = ("moe_act_zero_share",)
# lambda of ``loss = CE_main + lambda * CE_mtp``: DeepSeek-V3's value for its
# first 10 T tokens (arXiv:2412.19437 section 4.2); no config carries one.
MTP_LOSS_WEIGHT = 0.3
# Queries a block of attention, and positions a block of the output head
# (logits and loss are never held for the whole batch).  Not
# configuration: only tests, whose rows are shorter than a block, pass
# smaller ones to ``GatedMoEDecoder``.
ATTN_BLOCK = 512
HEAD_BLOCK = 1024
# Queries a block of the indexed attention, and blocks a run.  The
# ``jax.numpy`` bodies hold a block's float32 products in HBM for every
# head and worker at once (the indexer's 0.27 GB at 256 queries, two
# workers, 16 heads and 8,192 keys, the attention's 0.5 GB an array;
# PERF.md, PR 32); the fused kernels keep both in VMEM (PR 33, PR 35) and
# visit no tile of keys past a block's last query whatever the extent.
# ``INDEX_SPAN`` blocks in a row share one piece of code
# (``jax.lax.map``) and one extent of keys, the end of the last of them,
# which the selection, the alignment term and (in ``jax.numpy``) every
# product run over: 1 would compile every block apart, the whole row
# would make each block's [queries, keys] passes as long as the row.
INDEX_BLOCK = 256
INDEX_SPAN = 4
# Every matrix is normal(0, INITIALIZER_RANGE), every norm weight 1 (the
# published config carries no initializer).
INITIALIZER_RANGE = 0.02


def _keep(x):
    """``x`` under the name a layer's ``jax.checkpoint`` keeps."""
    return checkpoint_name(x, MATMUL_PRODUCTS)


def _rms(x, weight, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


def _inverse_frequencies(rope, head_dim: int):
    """([rotary_dim / 2] inverse frequencies, cos/sin scale) of one
    layer kind's ``rope_parameters`` entry: ``default``, or ``yarn``'s
    blend of interpolated and extrapolated frequencies by the linear
    ramp between the two correction dimensions."""
    dim = int(head_dim * rope.get("partial_rotary_factor", 1.0))
    theta = float(rope["rope_theta"])
    pos_freqs = theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    kind = rope.get("rope_type", "default")
    if kind == "default":
        return 1.0 / pos_freqs, 1.0
    if kind != "yarn":
        raise ValueError(f"unknown rope_type {kind!r}; one of default|yarn")
    original = rope["original_max_position_embeddings"]

    def correction_dim(rotations):
        return (dim * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(rope["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rope["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    inv = (ramp / (rope["factor"] * pos_freqs) + (1.0 - ramp) / pos_freqs)
    return inv, float(rope["attention_factor"])


def _angles(rope, width: int, t: int):
    """([t, rotary_dim / 2] float32 angles of positions ``0 .. t-1`` for
    a ``width``-lane head, the attention factor their cos and sin take):
    what every rotary body of one layer kind reads."""
    inv, scale = _inverse_frequencies(rope, width)
    return (jnp.arange(t, dtype=jnp.float32)[:, None]
            * jnp.asarray(inv, jnp.float32)[None, :]), scale


def _rotary(x, rope):
    """x: [H, T, head_dim]; ``rotate_half`` over the first rotary_dim
    dimensions of each head, float32.  Where ``rope["interleave"]`` the
    pairs are (2i, 2i + 1) instead of (i, i + rotary_dim / 2): they are
    taken apart into the two halves first, and stay apart in the result.
    That is the interleaved rotation with its dimensions in another
    order, the same order for every query and key, so every score is the
    interleaved one."""
    angles, scale = _angles(rope, x.shape[-1], x.shape[-2])
    angles = jnp.concatenate([angles, angles], axis=-1)
    cos, sin = jnp.cos(angles) * scale, jnp.sin(angles) * scale
    rd = angles.shape[-1]
    rot, rest = x[..., :rd], x[..., rd:]
    if rope.get("interleave"):
        rot = jnp.swapaxes(rot.reshape(*rot.shape[:-1], rd // 2, 2), -1,
                           -2).reshape(rot.shape)
    half = jnp.concatenate([-rot[..., rd // 2:], rot[..., :rd // 2]], -1)
    return jnp.concatenate([rot * cos + half * sin, rest], -1)


def _tables(rope, width: int, t: int):
    """(cos, sin) [t, rotary_dim / 2] float32 of those angles, times the
    attention factor: what the rotary kernels take."""
    angles, factor = _angles(rope, width, t)
    return jnp.cos(angles) * factor, jnp.sin(angles) * factor


def rotary_dim(rope, width: int) -> int:
    """Lanes of a ``width``-lane head (or part of one) that rotary turns:
    the first ``partial_rotary_factor`` of them."""
    return int(width * rope.get("partial_rotary_factor", 1.0))


def rotary_path(t: int, head_dim: int, rotary: int, *, nope: int = 0,
                normed: bool = False) -> str:
    """Which body turns the queries and keys of a layer, rows of ``t``
    positions and heads of ``head_dim`` lanes whose ``rotary`` rotary
    lanes follow ``nope`` lanes without rotary: ``"fused"``, the Pallas
    kernels of ``dopt.ops.rotary`` (rotary, the queries' scale and the
    cast to the compute dtype in one pass a tensor, forward and
    backward), where the rotary's input is a projection's product with no
    per-head norm in between (``normed``: the norm's backward asks for
    its own input, so norm and rotary would have to be one kernel) and
    the kernel's shape limits allow it (``rotary.fits``: the row whole
    tiles of 128 positions, the head at least a lane tile of 128, rotary
    lanes a multiple of 64 that start on a lane tile) -- else ``"jnp"``,
    ``_rotary`` and the scale and cast after it.  Nothing else decides
    it: no option, no platform (the kernels are interpreted on the
    CPU)."""
    fits = not normed and rotary_kernels.fits(t, head_dim, rotary, nope)
    return "fused" if fits else "jnp"


def _rope(x, rope, dtype, *, nope: int = 0, scale: float = 1.0):
    """``rotary_path``'s ``"fused"`` body: x [H, T, D] float32, a
    projection's product -> [H, T, D] in ``dtype`` as the attention takes
    it, under the name a layer keeps, in ONE kernel pass: rotary over the
    lanes from ``nope`` on (``_rotary``'s rotation; interleaved pairs keep
    the product's lane order, which ``_rotary`` takes apart: the same
    order for every query and key of a layer either way), the first
    ``nope`` lanes passed through, every lane times ``scale``."""
    _, t, d = x.shape
    return _keep(rotary_kernels.rotate(
        x, *_tables(rope, d - nope, t), rotary_dim=rotary_dim(rope, d - nope),
        interleave=bool(rope.get("interleave")), scale=scale, dtype=dtype,
        nope=nope))


@jax.checkpoint
def _attend_block(q, k, v, first_q, first_k, window):
    """Queries at positions ``first_q ...`` against keys at ``first_k
    ...``: q [G, R, Tq, D], k [G, Tk, D] and v [G, Tk, E] -> [G, R, Tq,
    E].  Scores and softmax in float32.  Every query sees at least
    itself, so no row of the mask is empty."""
    scores = jnp.einsum("grqd,gkd->grqk", q, k,
                        preferred_element_type=jnp.float32)
    scores = scores / math.sqrt(q.shape[-1])
    back = ((first_q + jnp.arange(q.shape[-2]))[:, None]
            - (first_k + jnp.arange(k.shape[-2]))[None, :])
    seen = (back >= 0) & (back < window)
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    return jnp.einsum("grqk,gke->grqe", probs.astype(v.dtype), v)


def blocked_causal_attention(q, k, v, *, window: int | None, block: int):
    """Causal attention of one row, a block of ``block`` queries at a
    time: q [G, R, T, D] (R query heads share each of the G key/value
    heads), k [G, T, D], v [G, T, E].  ``window`` (None = full) is how many
    positions back a query sees, itself included.  Block b multiplies
    its queries with the keys ``lo .. end of b`` only, ``lo`` the
    128-aligned start of the band for a window and 0 without one; T need
    not be a multiple of ``block``."""
    t = q.shape[-2]
    out = []
    for s in range(0, t, block):
        e = min(s + block, t)
        lo = 0 if window is None else max(0, (s - window + 1) // 128 * 128)
        out.append(_attend_block(q[:, :, s:e], k[:, lo:e], v[:, lo:e], s,
                                 lo, t if window is None else window))
    return jnp.concatenate(out, axis=-2)


def attention_path(t: int, head_dim: int, block: int = ATTN_BLOCK,
                   value_dim: int | None = None) -> str:
    """Which body ``causal_attention`` runs for a row of ``t`` positions:
    ``"splash"``, the fused kernel, wherever the kernel's own shape
    limits allow it — the row a multiple of twice ``block`` (keys go two
    blocks at a time), ``block`` and the value head (``value_dim``, the
    query and key head where None) multiples of the chip's 128 lanes, the
    query and key head a multiple of 64 (the latent attention's 192 next
    to values of 128 compiles and runs on the v5e; PERF.md) —
    else ``"blocked"``.  Nothing else decides it: no option, no platform
    (the kernel is interpreted on the CPU)."""
    value_dim = head_dim if value_dim is None else value_dim
    fits = (t % (2 * block) == 0 and block % 128 == 0
            and head_dim % 64 == 0 and value_dim % 128 == 0)
    return "splash" if fits else "blocked"


def query_scale(t: int, head_dim: int, block: int = ATTN_BLOCK,
                value_dim: int | None = None) -> float:
    """What the rotary kernel scales a query by for ``causal_attention``:
    1/sqrt(head_dim) where the fused attention takes pre-scaled queries
    (``attention_path``), 1 where the blocked body scales its scores."""
    splash = attention_path(t, head_dim, block, value_dim) == "splash"
    return 1.0 / math.sqrt(head_dim) if splash else 1.0


def causal_attention(q, k, v, *, window: int | None, block: int = ATTN_BLOCK,
                     scaled: bool = False):
    """Causal (``window`` None) or banded attention of one row: q
    [G * R, T, D] float32 and unscaled (R query heads share each of the
    G key/value heads) -- or, ``scaled``, as the rotary kernel leaves it:
    in the compute dtype, scaled by ``query_scale`` and kept --, k
    [G, T, D] and v [G, T, E] in the compute dtype -> [G, R, T, E].

    The ONE fork of the attention, and it exists only because the fused
    kernel cannot take every shape (``attention_path``).  Published
    shapes (head 128, rows that are multiples of 1,024: the benchmark's
    cell, ``--preset laguna-localsgd2``) take ``splash_causal_attention``
    and no score reaches HBM; toy rows of a few dozen positions and
    8-wide heads (the CPU parity tests against the plain reference, the
    rehearsal) take the same blocks in ``jax.numpy``.  The two are held
    to each other, outputs and gradients, in ``tests/test_decoder.py``,
    and a traced benchmark run shows which ran: ``attn_kernel_roofline``
    reads the ``splash_mqa_*`` kernels and is absent without them."""
    heads, t, d = q.shape
    grouped = (k.shape[0], heads // k.shape[0], t, d)
    splash = attention_path(t, d, block, v.shape[-1]) == "splash"
    if not scaled:
        # kept across a layer's remat as the body takes it: each body's
        # own cast, the queries' last float32 pass (XLA fuses it with the
        # rotary in front of it, and a fusion goes by its root)
        with jax.named_scope("dopt_rope"):
            q = _keep((q / math.sqrt(d) if splash else q).astype(k.dtype))
    if splash:
        with jax.named_scope("dopt_splash"):
            return splash_causal_attention(q.reshape(grouped), k, v,
                                           window=window, block=block)
    return blocked_causal_attention(q.reshape(grouped), k, v, window=window,
                                    block=block)


def splash_causal_attention(q, k, v, *, window: int | None, block: int):
    """``blocked_causal_attention``'s result from ONE fused kernel a
    pass (jax's Pallas TPU splash attention: forward, dq and dkv kernels
    with an online float32 softmax), so no score ever reaches HBM; it
    skips the blocks a causal or banded mask empties.  ``q`` comes
    pre-scaled by 1/sqrt(head_dim), in shapes ``attention_path`` calls
    ``"splash"``.  Its output and log-sum-exp carry the name
    ``ATTN_RESIDUALS``, which a layer's ``jax.checkpoint`` keeps so that
    the backward pass does not run the forward kernel again.  Compiled
    on tpu, interpreted on cpu (``dopt.ops.pallas_interpret``).  The
    values' head may be narrower than the queries' and keys' (jax's
    kernels read each from its operand).  The
    compiled kernels carry no jax name stack: a trace finds them by
    their own names, ``splash_mqa_fwd`` / ``_dq`` / ``_dkv``."""
    from jax.experimental.pallas.ops.tpu import splash_attention as splash

    from dopt.ops import pallas_interpret

    _, r, t, _ = q.shape
    one = (splash.CausalMask((t, t)) if window is None else
           splash.LocalMask((t, t), window_size=(window - 1, 0), offset=0))
    # Keys go through VMEM two query blocks at a time and the backward is
    # one kernel for dq, dk and dv (on the v5e: 2,515 ms a round of the
    # benchmark's cell against 2,696 with blocks of `block` and two
    # backward kernels; PERF.md, PR 28).
    sizes = splash.BlockSizes(
        block_q=block, block_kv=2 * block, block_kv_compute=block,
        block_q_dkv=block, block_kv_dkv=2 * block,
        block_kv_dkv_compute=block, use_fused_bwd_kernel=True)
    # (eagerly: the kernel object carries its mask tables as arrays, and
    # the custom_vjp below may not close over another trace's values)
    with jax.ensure_compile_time_eval():
        kernel = jax.vmap(splash.make_splash_mqa_single_device(
            splash.MultiHeadMask([one] * r), block_sizes=sizes,
            residual_checkpoint_name=ATTN_RESIDUALS,
            interpret=pallas_interpret()))

    # The kernels' products take the ambient matmul precision when they
    # are traced, forward and (later, outside this call) backward, and
    # Mosaic refuses bfloat16 operands at "highest", which is what a
    # parity check sets around the whole program.  bfloat16 products are
    # exact in float32 at any precision, so the kernels are pinned to the
    # default: the one program whatever the caller's precision.
    @jax.custom_vjp
    def attend(q, k, v):
        with jax.default_matmul_precision("default"):
            return kernel(q, k, v)

    def forward(q, k, v):
        with jax.default_matmul_precision("default"):
            return jax.vjp(kernel, q, k, v)

    def backward(pullback, g):
        with jax.default_matmul_precision("default"):
            return pullback(g)

    attend.defvjp(forward, backward)
    return attend(q, k, v)


def select_top_keys(index, seen, count):
    """[Tq, Tk] bool: for each query its ``count`` (>= 1, at most the
    keys it sees) visible keys with the largest index score, a tie going
    to the lower position; exactly ``count`` of them.  ``index`` [Tq, Tk]
    float32, ``seen`` [Tq, Tk] bool, ``count`` [Tq] int32.

    The ``count``-th largest score of a row is found by bisection on the
    float's bit pattern, mapped to an unsigned integer in the floats'
    order: 32 passes that each count the scores at or above a candidate.
    No sort and no gather: ``lax.top_k`` with 2,048 of 8,192 is a full
    sort on this chip, and its indices would still have to become a
    mask."""
    index = jnp.where(index == 0, 0.0, index)         # -0.0 ties with 0.0
    bits = jax.lax.bitcast_convert_type(index, jnp.uint32)
    order = jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))
    order = jnp.where(seen, order, jnp.uint32(0))     # under every float

    def refine(i, kth):
        trial = kth | (jnp.uint32(1 << 31) >> i.astype(jnp.uint32))
        enough = jnp.sum(order >= trial[:, None], axis=-1) >= count
        return jnp.where(enough, trial, kth)

    kth = jax.lax.fori_loop(0, 32, refine,
                            jnp.zeros(order.shape[0], jnp.uint32))
    above = order > kth[:, None]
    tied = order == kth[:, None]
    room = (count - jnp.sum(above, axis=-1)).astype(jnp.float32)
    return above | (tied & (_running_count(tied) <= room[:, None]))


def _apart(x):
    """``x``, computed apart from what takes it.  A row's maximum or sum
    that XLA:TPU fuses with the elementwise pass that takes it back
    becomes a ``reduce-window`` two rows wide: 8.4 s of a 10.4 s round of
    the benchmark's cell, where the passes apart take 0.3 (PERF.md,
    PR 32)."""
    return jax.lax.optimization_barrier(x)


def _masked_softmax(x, keep, log: bool = False):
    """``softmax`` (or ``log_softmax``) over the last axis of float32
    ``x`` where ``keep``, nothing (``-inf``) elsewhere; every row keeps
    at least one position."""
    x = jnp.where(keep, x, -jnp.inf)
    shifted = x - _apart(jax.lax.stop_gradient(
        jnp.max(x, axis=-1, keepdims=True)))
    total = _apart(jnp.sum(jnp.exp(shifted), axis=-1, keepdims=True))
    return shifted - jnp.log(total) if log else jnp.exp(shifted) / total


def _masked_attention(q, k, v, keep):
    """The sparse attention's body in ``jax.numpy``, the definition the
    fused kernels are held to: q [G, R, Tq, D], k and v [G, Tk, D], keep
    [Tq, Tk] bool -> (output [G, R, Tq, D], the head-mean [Tq, Tk] of the
    float32 probabilities, a constant under differentiation).  Each
    float32 [G, R, Tq, Tk] array passes through HBM."""
    scores = jnp.einsum("grqd,gkd->grqk", q, k,
                        preferred_element_type=jnp.float32)
    scores = scores / math.sqrt(q.shape[-1])
    probs = _masked_softmax(scores, keep)
    out = jnp.einsum("grqk,gkd->grqd", probs.astype(v.dtype), v)
    return out, jax.lax.stop_gradient(jnp.mean(probs, axis=(0, 1)))


def _index_scores(qi, ki, wi):
    """The lightning indexer's scores in ``jax.numpy``, the definition the
    fused kernels are held to: qi [J, Tq, E], ki [Tk, E], wi [Tq, J]
    float32 -> float32 [Tq, Tk], ``sum_j wi[q, j] * relu(qi[j, q] .
    ki[k])``.  The float32 [J, Tq, Tk] products pass through HBM, forward
    and backward."""
    dots = jnp.einsum("jqe,ke->jqk", qi, ki,
                      preferred_element_type=jnp.float32)
    return jnp.sum(jax.nn.relu(dots) * wi.T[:, :, None], axis=0)


def indexed_attention_path(t: int, head_dim: int, index_heads: int,
                           block: int = INDEX_BLOCK) -> str:
    """Which bodies the indexed attention's blocks run for a row of ``t``
    positions: ``"indexed-fused"``, the Pallas kernels of
    ``dopt.ops.sparse_attention`` for the index scores and for what
    follows the selection, wherever their shape limits allow it -- the
    row whole blocks, ``block`` and the head multiples of the chip's 128
    lanes, the ``index_heads`` indexer heads' queries of a block within
    the 4,096 lanes the index kernels hold side by side -- else
    ``"indexed"``, the ``jax.numpy`` bodies.  Nothing else decides it: no
    option, no platform (the kernels are interpreted on the CPU)."""
    fits = (t % block == 0 and sparse_attention.fits(block, t, head_dim)
            and sparse_attention.index_fits(block, t, index_heads))
    return "indexed-fused" if fits else "indexed"


def _indexed_block(q, k, v, qi, ki, wi, first, *, topk: int, fused: bool):
    """One block of queries, at positions ``first ...``, of the indexed
    attention against the keys ``0 .. Tk-1`` (every key the block can
    see): q [G, R, Tq, D], k and v [G, Tk, D], and the indexer's qi
    [J, Tq, E], ki [Tk, E] in the compute dtype, wi [Tq, J] float32 ->
    (attention output [G, R, Tq, D], the block's alignment sum, its count
    of selected keys).  Scores, selection, softmax and the alignment
    term in float32.

    ``fused`` (``indexed_attention_path``) says which bodies run, the
    index scores' under ``dopt_index`` and the attention's under
    ``dopt_attend``.  The ``jax.numpy`` ones (``_index_scores``,
    ``_masked_attention``) are the definitions.  The kernels keep the
    [J, Tq, Tk] products and the attention's scores in VMEM, forward and
    backward, and visit no tile of keys past the block's last query (the
    index scores are exact zeros there; ``seen`` and ``chosen`` mask them
    here as they mask the definition's); the attention's hand back the
    head-mean from a kernel of its own and put the output and the
    log-sum-exp under ``ATTN_RESIDUALS``, so that a recompute runs the
    index scores, the selection and the head-mean again and not the
    attention."""
    at = first + jnp.arange(q.shape[-2])
    seen = jnp.arange(k.shape[-2])[None, :] <= at[:, None]
    with jax.named_scope("dopt_index"):
        index = (sparse_attention.index_scores(qi, ki, wi, first) if fused
                 else _index_scores(qi, ki, wi))
        if k.shape[-2] > topk:
            with jax.named_scope("dopt_select"):
                chosen = select_top_keys(jax.lax.stop_gradient(index), seen,
                                         jnp.minimum(at + 1, topk))
        else:                    # statically: every visible key is kept
            chosen = seen
    with jax.named_scope("dopt_attend"):
        if fused:
            out, target = sparse_attention.masked_attention(
                q, k, v, chosen, first, residual_name=ATTN_RESIDUALS)
        else:
            out, target = _masked_attention(q, k, v, chosen)
    with jax.named_scope("dopt_index"):
        logp = _masked_softmax(index, chosen, log=True)
        align = jnp.where(
            chosen, jax.scipy.special.xlogy(target, target) - target * logp,
            0.0)
    return out, jnp.sum(align), jnp.sum(chosen.astype(jnp.float32))


def indexed_causal_attention(q, k, v, qi, ki, wi, *, topk: int,
                             block: int = INDEX_BLOCK, project=None):
    """Causal attention of one row in which query t attends the
    ``min(t + 1, topk)`` keys its indexer scores highest (the third body
    of the attention: the mask comes from the data, and the kernels that
    take it are this repo's own, ``dopt.ops.sparse_attention``): q
    [G, R, T, D], k and v [G, T, D], qi [J, T, E], ki [T, E], wi [T, J]
    -> (output [G, R, T, D], the row's alignment term, its share of
    selected among visible keys).  ``project`` (a run's outputs
    [blocks, G, R, block, D] -> [blocks * block, d]) is applied before
    the runs are joined and its rows [T, d] come back in the output's
    place: a layer hands in its output projection, because the joined
    attention outputs would stand beside the kept ones, a second copy of
    all of them, in the layer's backward pass (0.29 GB of the benchmark
    cell's round, compile only; PERF.md, PR 33).

    A block of ``block`` queries at a time, each ``jax.checkpoint``-ed,
    ``INDEX_SPAN`` blocks in a row through one ``jax.lax.map`` against
    the keys up to the end of the last of them; T need not be a multiple
    of ``block`` (the remainder is a block of its own).  Which bodies a
    block runs, the index scores' and the attention's, is
    ``indexed_attention_path``'s to say, from the shapes alone; the
    kernels and the ``jax.numpy`` definitions are held to each other,
    scores, outputs, head-means and gradients, in
    ``tests/test_decoder.py``, and a traced run shows which ran by the
    kernels' names."""
    t = q.shape[-2]
    fused = indexed_attention_path(t, q.shape[-1], qi.shape[0],
                                   block) == "indexed-fused"
    # A block's checkpoint keeps what the kernels put under the name (the
    # jax.numpy body puts nothing there): the output and the log-sum-exp.
    body = jax.checkpoint(
        functools.partial(_indexed_block, topk=topk, fused=fused),
        policy=jax.checkpoint_policies.save_only_these_names(ATTN_RESIDUALS))
    edges = list(range(0, t - t % block, block * INDEX_SPAN))
    runs = [(s, min(s + block * INDEX_SPAN, t - t % block), block)
            for s in edges]
    if t % block:
        runs.append((t - t % block, t, t % block))
    outs, aligns, kepts = [], [], []
    for s, e, size in runs:
        n = (e - s) // size

        def blocks(x, axis):
            """[n, ...]: the run's queries of ``x``, a block a row."""
            x = jax.lax.slice_in_dim(x, s, e, axis=axis)
            x = x.reshape(*x.shape[:axis], n, size, *x.shape[axis + 1:])
            return jnp.moveaxis(x, axis, 0)

        out, align, kept = jax.lax.map(
            lambda b, e=e: body(b[0], k[:, :e], v[:, :e], b[1], ki[:e],
                                b[2], b[3]),
            (blocks(q, 2), blocks(qi, 1), blocks(wi, 0),
             s + size * jnp.arange(n)))
        if not fused:      # (the kernels name theirs, a block at a time)
            out = checkpoint_name(out, ATTN_RESIDUALS)
        outs.append(project(out) if project else jnp.moveaxis(
            out, 0, 2).reshape(*q.shape[:2], e - s, -1))
        aligns.append(jnp.sum(align))
        kepts.append(jnp.sum(kept))
    out = jnp.concatenate(outs, axis=0 if project else 2)
    return out, sum(aligns) / t, sum(kepts) / (t * (t + 1) / 2)


def _gated_mlp(p, x, dtype):
    g = _keep(jnp.dot(x, p["gate"].astype(dtype)))
    u = _keep(jnp.dot(x, p["up"].astype(dtype)))
    return jnp.dot(jax.nn.silu(g) * u, p["down"].astype(dtype))


class GatedMoEDecoder:
    """See the module docstring.  ``vocab_rows`` is the slice of the
    vocabulary this worker holds (ids, logits and loss are over it).
    ``attn_block`` None is the body's own (``ATTN_BLOCK``, or
    ``INDEX_BLOCK`` where the layers have an indexer)."""

    def __init__(self, cfg: DecoderConfig, *, vocab_rows: int,
                 dtype=jnp.float32, attn_block: int | None = None,
                 head_block: int = HEAD_BLOCK):
        self.cfg = cfg
        self.vocab_rows = vocab_rows
        self.dtype = jnp.dtype(dtype)
        self.attn_block = attn_block or (INDEX_BLOCK if cfg.indexed
                                         else ATTN_BLOCK)
        self.head_block = head_block
        self.experts_held = (cfg.experts if cfg.experts_held is None
                             else cfg.experts_held)
        self.counters = (COUNTERS + (INDEX_COUNTERS if cfg.indexed else ())
                         + (MTP_COUNTERS if cfg.mtp else ())
                         + (ACT_COUNTERS if cfg.expert_act == "relu" else ()))
        self._bodies = {}
        self.kind_traces = collections.Counter()

    def attention_path(self, t: int) -> str:
        """``"indexed-fused"`` or ``"indexed"``, ``"splash"`` or
        ``"blocked"`` for rows of ``t`` positions (``dopt.run`` prints it
        beside the device)."""
        c = self.cfg
        if c.indexed:
            return indexed_attention_path(
                t, c.head_dim, c.sa_config["indexer_num_heads"],
                self.attn_block)
        if c.latent:
            return attention_path(t, c.qk_nope_head_dim + c.qk_rope_head_dim,
                                  self.attn_block, c.v_head_dim)
        return attention_path(t, c.head_dim, self.attn_block)

    def rotary_path(self, t: int) -> str:
        """``"fused(k/n kinds)"`` where k of the n layer kinds turn their
        queries and keys with the rotary kernels at rows of ``t``
        positions, ``"jnp"`` where none does (``dopt.run`` prints it
        beside the device; ``rotary_path`` is the rule; a kind without
        rotary counts among the n)."""
        c = self.cfg
        nope = c.qk_nope_head_dim if c.latent else 0
        d = nope + c.head_dim
        paths = [rotary_path(t, d, rotary_dim(c.rope(i[0]), d - nope),
                             nope=nope, normed=c.indexed)
                 for i in self.layer_kinds().values()
                 if c.rope(i[0]) is not None]
        fused = paths.count("fused")
        kinds = len(self.layer_kinds())
        return f"fused({fused}/{kinds} kinds)" if fused else "jnp"

    @property
    def mtp_layer(self) -> int:
        """The index the multi-token-prediction module's block has among
        the layers (after every layer: one with experts)."""
        return max(self.cfg.num_hidden_layers,
                   self.cfg.first_k_dense_replace or 0)

    # ---------------------------------------------------------- params
    def init(self, key, dummy=None):
        """``{"params": tree}``, float32: normal(0, INITIALIZER_RANGE)
        matrices, unit norm weights.  ``dummy`` is ignored (the engines
        pass a sample for flax models)."""
        c = self.cfg
        d, hd, kv = c.hidden_size, c.head_dim, c.num_key_value_heads
        blocks = c.num_hidden_layers + c.mtp
        keys = iter(jax.random.split(key, 16 * blocks + 2))

        def mat(*shape):
            return INITIALIZER_RANGE * jax.random.normal(
                next(keys), shape, jnp.float32)

        def mlp(width, *lead):
            return {"gate": mat(*lead, d, width), "up": mat(*lead, d, width),
                    "down": mat(*lead, width, d)}

        def latent(h):
            """A multi-head latent attention's leaves: two down-projections
            with their norms, two up-projections, the output projection."""
            qk = c.qk_nope_head_dim + c.qk_rope_head_dim
            return {"attn_norm": jnp.ones(d), "q_a": mat(d, c.q_lora_rank),
                    "q_a_norm": jnp.ones(c.q_lora_rank),
                    "q_b": mat(c.q_lora_rank, h * qk),
                    "kv_a": mat(d, c.kv_lora_rank + c.qk_rope_head_dim),
                    "kv_a_norm": jnp.ones(c.kv_lora_rank),
                    "kv_b": mat(c.kv_lora_rank,
                                h * (c.qk_nope_head_dim + c.v_head_dim)),
                    "o": mat(h * c.v_head_dim, d), "mlp_norm": jnp.ones(d)}

        params = {"embed": mat(self.vocab_rows, d)}
        for i in range(blocks):
            if i == c.num_hidden_layers:      # the MTP module's block
                i = self.mtp_layer
            h = c.query_heads(i)
            if c.latent:
                layer = latent(h)
            else:
                layer = {"attn_norm": jnp.ones(d), "q": mat(d, h * hd),
                         "k": mat(d, kv * hd), "v": mat(d, kv * hd)}
            if c.output_gate:
                layer["gate"] = mat(d, h)
            if not c.latent:
                layer.update(o=mat(h * hd, d), mlp_norm=jnp.ones(d))
            if c.indexed:
                j, e = (c.sa_config[n] for n in ("indexer_num_heads",
                                                 "indexer_head_dim"))
                layer.update(
                    q_norm=jnp.ones(hd), k_norm=jnp.ones(hd),
                    indexer={"q": mat(d, j * e), "k": mat(d, e),
                             "k_norm": jnp.ones(e), "k_bias": jnp.zeros(e),
                             "w": mat(d, j)})
            if not c.sparse_mlp(i):
                layer["mlp"] = mlp(c.intermediate_size)
            else:
                layer["router"] = mat(d, c.experts)
                if c.latent:      # chooses; held at 0 (no gradient)
                    layer["router_bias"] = jnp.zeros(c.experts)
                if c.shared_width:
                    layer["shared"] = mlp(c.shared_width)
                layer["experts"] = mlp(c.expert_width, self.experts_held)
            if i == self.mtp_layer:
                params["mtp"] = {"enorm": jnp.ones(d), "hnorm": jnp.ones(d),
                                 "eh_proj": mat(2 * d, d), "layer": layer,
                                 "norm": jnp.ones(d)}
            else:
                params[f"layer{i}"] = layer
        params["norm"] = jnp.ones(d)
        params["head"] = mat(d, self.vocab_rows)
        return {"params": params}

    # ---------------------------------------------------------- layers
    def _index(self, p, x, rope):
        """The lightning indexer's inputs to the selection, from the
        DETACHED normed layer input x [T, d]: (qi [J, T, E], ki [T, E] in
        the compute dtype, wi [T, J] float32).  ONE key head, a layer
        norm on it, the layer's rotary over all E dimensions."""
        c, dt = self.cfg, self.dtype
        j, e = (c.sa_config[n] for n in ("indexer_num_heads",
                                         "indexer_head_dim"))
        with jax.named_scope("dopt_index"):
            x = jax.lax.stop_gradient(x)
            qi = _keep(jnp.einsum(
                "td,dje->jte", x, p["q"].astype(dt).reshape(-1, j, e),
                preferred_element_type=jnp.float32))
            ki = _keep(jnp.dot(x, p["k"].astype(dt),
                               preferred_element_type=jnp.float32))
            ki = ki - jnp.mean(ki, -1, keepdims=True)
            ki = (ki * jax.lax.rsqrt(jnp.mean(ki * ki, -1, keepdims=True)
                                     + c.rms_norm_eps)
                  * p["k_norm"] + p["k_bias"])
            wi = _keep(jnp.dot(x, p["w"].astype(dt),
                               preferred_element_type=jnp.float32))
            return (_rotary(qi, rope).astype(dt),
                    _rotary(ki[None], rope)[0].astype(dt),
                    wi * (j ** -0.5 * e ** -0.5))

    def _latent_attention(self, p, x, rope):
        """Multi-head latent attention (DeepSeek-V3, arXiv:2412.19437
        section 2.1.1) of the normed layer input x [T, d] in the compute
        dtype -> [T, d] float32 before the residual add.  Queries through
        the ``q_lora_rank`` bottleneck, keys and values through the
        ``kv_lora_rank`` one, each normed in the middle; a query and a key
        head are ``qk_nope_head_dim`` dimensions without rotary and
        ``qk_rope_head_dim`` with it, and the rotary part of the key is
        ONE vector a position that every head shares; values are
        ``v_head_dim`` wide."""
        c, dt = self.cfg, self.dtype
        t = x.shape[0]
        heads, nope = c.num_attention_heads, c.qk_nope_head_dim
        rank, vd = c.kv_lora_rank, c.v_head_dim
        with jax.named_scope("dopt_qkv"):
            with jax.named_scope("dopt_latent"):
                # the norms' backward asks for the float32 products
                cq = _keep(jnp.dot(x, p["q_a"].astype(dt),
                                   preferred_element_type=jnp.float32))
                cq = _rms(cq, p["q_a_norm"], c.rms_norm_eps).astype(dt)
                ckv = _keep(jnp.dot(x, p["kv_a"].astype(dt),
                                    preferred_element_type=jnp.float32))
                c_kv = _rms(ckv[:, :rank], p["kv_a_norm"],
                            c.rms_norm_eps).astype(dt)
            q = jnp.einsum("tr,rne->nte", cq,
                           p["q_b"].astype(dt).reshape(-1, heads,
                                                       nope + c.head_dim),
                           preferred_element_type=jnp.float32)
            kv = jnp.einsum("tr,rne->nte", c_kv,
                            p["kv_b"].astype(dt).reshape(-1, heads, nope + vd),
                            preferred_element_type=jnp.float32)
        qk = nope + c.head_dim
        fused = rotary_path(t, qk, rotary_dim(rope, c.head_dim),
                            nope=nope) == "fused"
        with jax.named_scope("dopt_rope"):
            if fused:
                q = _rope(q, rope, dt, nope=nope, scale=query_scale(
                    t, qk, self.attn_block, vd))
                k, v = map(_keep, rotary_kernels.latent_keys(
                    kv, ckv[:, rank:], *_tables(rope, c.head_dim, t),
                    nope=nope, interleave=bool(rope.get("interleave")),
                    dtype=dt))
            else:
                q = jnp.concatenate([q[..., :nope],
                                     _rotary(q[..., nope:], rope)], -1)
                shared = _rotary(ckv[None, :, rank:], rope)      # [1, T, rd]
                k = _keep(jnp.concatenate(
                    [kv[..., :nope], jnp.broadcast_to(
                        shared, (heads, t, c.head_dim))], -1).astype(dt))
                v = _keep(kv[..., nope:].astype(dt))
        out = causal_attention(q, k, v, window=None, block=self.attn_block,
                               scaled=fused)
        with jax.named_scope("dopt_oproj"):
            return jnp.einsum("nte,ned->td", out.reshape(heads, t, vd),
                              p["o"].astype(dt).reshape(heads, vd, -1),
                              preferred_element_type=jnp.float32)

    def _attention(self, p, h, i, a=None):
        """-> (the residual stream after the layer's attention, None or
        the indexer's counts); ``a`` the layer input's norm where the
        caller has taken it."""
        c, dt = self.cfg, self.dtype
        t = h.shape[0]
        hd, kv = c.head_dim, c.num_key_value_heads
        heads = c.query_heads(i)
        rope = c.rope(i)
        with jax.named_scope("dopt_attn"):
            if a is None:
                a = _rms(h, p["attn_norm"], c.rms_norm_eps)
            x = a.astype(dt)

            def heads_of(name, n):
                """[n, T, head_dim] float32: head-major, the layout the
                attention wants, straight out of the projection."""
                return jnp.einsum(
                    "td,dne->nte", x, p[name].astype(dt).reshape(-1, n, hd),
                    preferred_element_type=jnp.float32)

            # (each part's scope opens around its ops where the layer runs
            # them: a scope names ops and moves none)
            if c.latent:
                out, counts = self._latent_attention(p, x, rope), None
            elif c.indexed:
                # per-head RMS norms, whose backward asks for the products
                with jax.named_scope("dopt_qkv"):
                    q = _keep(heads_of("q", heads))
                with jax.named_scope("dopt_rope"):
                    q = _rotary(_rms(q, p["q_norm"], c.rms_norm_eps),
                                rope).astype(dt)
                with jax.named_scope("dopt_qkv"):
                    k = _keep(heads_of("k", kv))
                with jax.named_scope("dopt_rope"):
                    k = _rotary(_rms(k, p["k_norm"], c.rms_norm_eps),
                                rope).astype(dt)
                with jax.named_scope("dopt_qkv"):
                    v = _keep(heads_of("v", kv).astype(dt))
                with jax.named_scope("dopt_oproj"):
                    wo = p["o"].astype(dt).reshape(kv, heads // kv, hd, -1)

                def project(out):
                    """A run of blocks at a time: see ``project`` there."""
                    with jax.named_scope("dopt_oproj"):
                        return jnp.einsum(
                            "bgrqe,gred->bqd", out, wo,
                            preferred_element_type=jnp.float32
                        ).reshape(-1, wo.shape[-1])

                out, align, kept = indexed_causal_attention(
                    q.reshape(kv, heads // kv, t, hd), k, v,
                    *self._index(p["indexer"], x, rope),
                    topk=c.sa_config["topk"], block=self.attn_block,
                    project=project)
                counts = dict(zip(INDEX_COUNTERS, (align, kept)))
            else:
                # (a layer without rotary, NoPE: q and k as projected; the
                # queries' scale and cast are then causal_attention's)
                fused = (rope is not None and rotary_path(
                    t, hd, rotary_dim(rope, hd)) == "fused")
                with jax.named_scope("dopt_qkv"):
                    q = heads_of("q", heads)
                with jax.named_scope("dopt_rope"):
                    if fused:
                        q = _rope(q, rope, dt, scale=query_scale(
                            t, hd, self.attn_block))
                    elif rope is not None:
                        q = _rotary(q, rope)
                with jax.named_scope("dopt_qkv"):
                    k = heads_of("k", kv)
                with jax.named_scope("dopt_rope"):
                    k = (_rope(k, rope, dt) if fused else _keep(
                        (k if rope is None else _rotary(k, rope)).astype(dt)))
                with jax.named_scope("dopt_qkv"):
                    v = _keep(heads_of("v", kv).astype(dt))
                out = causal_attention(q, k, v, window=c.window(i),
                                       block=self.attn_block, scaled=fused)
                if c.output_gate:
                    with jax.named_scope("dopt_qkv"):
                        gate = _keep(jnp.einsum(
                            "td,dn->nt", x, p["gate"].astype(dt),
                            preferred_element_type=jnp.float32))
                with jax.named_scope("dopt_oproj"):
                    if c.output_gate:
                        gate = jax.nn.sigmoid(gate)
                        out = (out.reshape(heads, t, hd)
                               * gate[..., None].astype(dt))
                    else:
                        out = out.reshape(heads, t, hd)
                    out = jnp.einsum(
                        "nte,ned->td", out,
                        p["o"].astype(dt).reshape(heads, hd, -1),
                        preferred_element_type=jnp.float32)
                counts = None
            return _keep(h + out), counts

    def _route(self, router, m, bias=None):
        """-> ([T, held] combine weights, 0 where a token was not routed
        to that held expert; [T, held] bool, whether it was; the step's
        routing counts).  The chosen experts are kept across a layer's
        remat and the scores taken at them (``top_k``'s own values and
        gradients, bit for bit), so the backward pass runs no second
        ``top_k``.  Where the layer has a correction ``bias`` it chooses by
        score plus bias and weighs by the score alone (DeepSeek-V3's
        auxiliary-loss-free balancing, ``topk_method: noaux_tc``); the
        bias gets no gradient."""
        c = self.cfg
        scores = _keep(jnp.dot(m, router,
                               precision=jax.lax.Precision.HIGHEST))
        # sigmoid scores, or a softmax over the published experts
        scores = (jax.nn.softmax(scores, axis=-1) if c.softmax_router
                  else jax.nn.sigmoid(scores))
        choose = scores if bias is None else scores + bias
        idx = _keep(jax.lax.top_k(jax.lax.stop_gradient(choose),
                                  c.experts_per_tok)[1])
        # (a masked sum of one term: a gather of single elements, and the
        # scatter-add that is its gradient, cost this chip 6 ns each)
        top = jnp.sum(jnp.where(
            idx[..., None] == jnp.arange(scores.shape[-1]),
            scores[:, None, :], 0.0), axis=-1)
        top = top / jnp.sum(top, -1, keepdims=True)       # renormalised
        if c.routed_scale is not None:
            top = top * c.routed_scale
        # [T, k, held]: slot j of token t reached held expert e
        hit = ((idx - c.expert_offset)[..., None]
               == jnp.arange(self.experts_held)).astype(jnp.float32)
        load = hit.sum(axis=(0, 1))                       # [held] slots
        counts = {
            "moe_held_slot_share": load.sum() / idx.size,
            "moe_load_max_over_mean":
                load.max() / jnp.maximum(load.mean(), 1.0 / idx.size),
        }
        return (jnp.sum(hit * top[..., None], axis=1),
                jnp.sum(hit, axis=1) > 0, counts)

    def expert_path(self) -> str:
        """``"grouped-fused"`` or ``"grouped"``: which body the held
        experts run (``dopt.run`` prints it beside the device)."""
        return grouped_experts.path(self.cfg.hidden_size,
                                    self.cfg.expert_width)

    def _preroute(self, p, a):
        """``_route`` of the layer's normed input ``a`` [T, d] float32,
        before the attention (``router_first``), under ``dopt_preroute``
        inside ``dopt_moe`` / ``dopt_route``."""
        with jax.named_scope("dopt_moe"), jax.named_scope("dopt_route"):
            with jax.named_scope("dopt_preroute"):
                return self._route(p["router"], a, p.get("router_bias"))

    def _experts(self, p, m, routed=None):
        """-> (the layer's expert part of the MLP's normed input m, the
        routing counts); ``routed`` is ``_route``'s result where the
        router ran before the attention, else the router reads m."""
        dt = self.dtype
        with jax.named_scope("dopt_moe"):
            if routed is None:
                with jax.named_scope("dopt_route"):
                    routed = self._route(p["router"], m, p.get("router_bias"))
            weight, hit, counts = routed
            if "shared" in p:
                with jax.named_scope("dopt_shared"):
                    out = _gated_mlp(p["shared"], m.astype(dt), dt
                                     ).astype(jnp.float32)
            else:
                out = jnp.zeros_like(m)
            out, zeros = grouped_experts.grouped_experts(
                m, hit, weight, p["experts"], out,
                k=self.cfg.experts_per_tok, dtype=dt,
                keep_name=MATMUL_PRODUCTS, act=self.cfg.expert_act)
            if zeros is not None:
                counts = {**counts, ACT_COUNTERS[0]: zeros}
            return out, counts

    def _layer(self, p, h, i):
        """-> (the residual stream after layer i, its counts: the
        indexer's and the router's, {} where it has neither).  Where the
        router reads the layer's input (``router_first``) it runs on the
        attention's normed input before the attention, and the experts
        on the MLP's norm of the stream after it."""
        c = self.cfg
        routed = a = None
        if c.router_first:
            with jax.named_scope("dopt_attn"):
                a = _rms(h, p["attn_norm"], c.rms_norm_eps)
            routed = self._preroute(p, a)
        h, counts = self._attention(p, h, i, a)
        counts = counts or {}
        m = _rms(h, p["mlp_norm"], c.rms_norm_eps)
        if not c.sparse_mlp(i):
            with jax.named_scope("dopt_mlp"):
                out = _gated_mlp(p["mlp"], m.astype(self.dtype), self.dtype
                                 ).astype(jnp.float32)
            return h + out, counts
        out, routing = self._experts(p, m, routed)
        return h + out, {**counts, **routing}

    def _kind(self, i: int) -> tuple:
        """What ``_layer`` reads of ``i``: query heads, rotary, window,
        sparse or dense MLP, indexer or not.  Layers of one kind compute
        the same function of (their parameters, the stream)."""
        c = self.cfg
        return (c.query_heads(i), json.dumps(c.rope(i), sort_keys=True),
                c.window(i), c.sparse_mlp(i), c.indexed)

    def layer_kinds(self) -> dict:
        """{kind: [its layers]}, in the order of their first layers
        (``dopt.run`` prints how many of each there are)."""
        kinds = {}
        for i in range(self.cfg.num_hidden_layers):
            kinds.setdefault(self._kind(i), []).append(i)
        return kinds

    def _body(self, i: int):
        """Layer ``i``'s kind's one ``jax.jit(jax.checkpoint(...))``, made
        once per model: every layer of the kind (the multi-token-prediction
        module's block among them, ``mtp_layer``) calls the same object, so
        jax traces, differentiates, batches and lowers the layer once per
        kind and shape, not once per layer (XLA inlines the calls).
        ``kind_traces`` counts the traces by kind."""
        kind = self._kind(i)
        if kind not in self._bodies:
            first = self.layer_kinds().get(kind, [i])[0]

            def layer(p, h):
                self.kind_traces[kind] += 1
                return self._layer(p, h, first)

            self._bodies[kind] = jax.jit(jax.checkpoint(
                layer, policy=jax.checkpoint_policies.save_only_these_names(
                    *LAYER_KEEPS)))
        return self._bodies[kind]

    def _hidden(self, params, tokens):
        """One row: [T] ids -> ([T, d] float32 hidden state before the
        final norm, each layer count averaged over the layers that have
        it)."""
        h = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
        counts = []
        for i in range(self.cfg.num_hidden_layers):
            h, c = self._body(i)(params[f"layer{i}"], h)
            counts.append(c)
        return h, {k: (jnp.mean(jnp.stack([c[k] for c in counts if k in c]))
                       if any(k in c for c in counts) else jnp.zeros(()))
                   for k in self.counters if k not in MTP_COUNTERS}

    def _logits(self, params, h):
        x = _rms(h, params["norm"], self.cfg.rms_norm_eps).astype(self.dtype)
        return jnp.dot(x, params["head"].astype(self.dtype),
                       preferred_element_type=jnp.float32)

    def _mtp(self, params, h, nxt):
        """The multi-token-prediction module (DeepSeek-V3 section 2.2,
        depth 1) on one row: the main model's hidden state h [T, d]
        before its final norm and the next tokens' ids ``nxt`` [T] (the
        labels; negative ones are taken as id 0 and not counted) -> the
        module's hidden state [T, d] before its own final norm, and its
        block's routing counts.  The two streams are normed and joined,
        the embedding's first as the published checkpoints have them
        (``[enorm(Emb(t_{i+1})) ; hnorm(h_i)] eh_proj``), and go through
        one more layer."""
        c, dt, p = self.cfg, self.dtype, params["mtp"]
        e = jnp.take(params["embed"], jnp.maximum(nxt, 0), axis=0)
        x = jnp.concatenate([_rms(e, p["enorm"], c.rms_norm_eps),
                             _rms(h, p["hnorm"], c.rms_norm_eps)], -1)
        x = jnp.dot(x.astype(dt), p["eh_proj"].astype(dt),
                    preferred_element_type=jnp.float32)
        return self._body(self.mtp_layer)(p["layer"], x)

    def _head_loss(self, out, h, labels, counted):
        """The head and the loss a block of ``head_block`` positions at a
        time: ``out`` {"norm", "head"}, h [B, T, d], labels [B, T] (ids,
        negative where ``counted`` is 0), counted [B, T] float32 -> (each
        block's summed negative log-likelihood, each block's hits)."""
        n = counted.size
        blk = min(self.head_block, n)
        pad = -n % blk

        def blocks(x):
            x = x.reshape(n, *x.shape[2:])
            return jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1)
                           ).reshape((n + pad) // blk, blk, *x.shape[1:])

        @jax.checkpoint
        def block(out, hb, yb, cb):
            logp = jax.nn.log_softmax(self._logits(out, hb), axis=-1)
            yb = jnp.maximum(yb, 0)
            nll = -jnp.take_along_axis(logp, yb[:, None], axis=-1)[:, 0]
            hit = (jnp.argmax(logp, axis=-1) == yb).astype(jnp.float32)
            return jnp.sum(nll * cb), jnp.sum(hit * cb)

        return jax.lax.map(
            lambda b: block(out, *b),
            (blocks(h), blocks(labels.astype(jnp.int32)), blocks(counted)))

    # --------------------------------------------------------- surface
    def apply(self, variables, tokens):
        """[B, T] int32 ids -> [B, T, V] float32 logits (whole: for
        tests and inference, not the training path)."""
        params = variables["params"]
        h, _ = jax.vmap(lambda row: self._hidden(params, row))(tokens)
        return self._logits(params, h)

    def loss(self, params, tokens, labels, weights):
        """The token contract (``benchmark/reference.py``): [B, T] ids,
        [B, T] labels (negative = not counted), [B] 0/1 row weights ->
        (sum of the counted positions' negative log-likelihood over
        their count, {"acc": next-token accuracy over the counted
        positions, **counts}).  Layers with an indexer add their
        alignment terms, each a mean over the positions of the rows that
        count: the indexer's leaves get that term's gradient alone and
        every other leaf the cross-entropy's alone (the indexer's input
        is detached and the selection is not differentiated).  A
        multi-token-prediction module adds ``MTP_LOSS_WEIGHT`` times its
        cross-entropy against the token after next (``labels`` one
        position on), over the positions whose label and next label both
        count; it reaches the shared embedding and head."""
        h, counts = jax.vmap(lambda row: self._hidden(params, row))(tokens)
        counted = (weights[:, None] * (labels >= 0)).astype(jnp.float32)
        with jax.named_scope("dopt_head"):
            nll, hit = self._head_loss(
                {k: params[k] for k in ("norm", "head")}, h, labels, counted)
            total = jnp.maximum(jnp.sum(counted), 1.0)
            aux = {"acc": jnp.sum(hit) / total,
                   **{k: jnp.mean(v) for k, v in counts.items()}}
            loss = jnp.sum(nll) / total
        if self.cfg.mtp:
            with jax.named_scope("dopt_mtp"):
                g, block = jax.vmap(lambda hr, yr: self._mtp(params, hr,
                                                             yr))(h, labels)
                after = jnp.concatenate(
                    [labels[:, 1:], jnp.full_like(labels[:, :1], -1)], 1)
                both = counted * (after >= 0)
                with jax.named_scope("dopt_head"):
                    nll, hit = self._head_loss(
                        {"norm": params["mtp"]["norm"],
                         "head": params["head"]}, g, after, both)
                total = jnp.maximum(jnp.sum(both), 1.0)
            # the block's routing counts join the layers' means
            n = sum(map(self.cfg.sparse_mlp,
                        range(self.cfg.num_hidden_layers)))
            aux.update({k: (aux[k] * n + jnp.mean(block[k])) / (n + 1)
                        for k in COUNTERS},
                       mtp_loss=jnp.sum(nll) / total,
                       mtp_acc=jnp.sum(hit) / total)
            loss = loss + MTP_LOSS_WEIGHT * aux["mtp_loss"]
        if self.cfg.indexed:
            rows = weights.astype(jnp.float32)
            aux["index_align_loss"] = (
                jnp.sum(rows * counts["index_align_loss"])
                / jnp.maximum(jnp.sum(rows), 1.0))
            loss = loss + self.cfg.num_hidden_layers * aux["index_align_loss"]
        return loss, aux
