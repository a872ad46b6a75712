"""A gated window/full-attention mixture-of-experts decoder as ONE
worker of the gossip engine (``model="laguna"``, configured by
``dopt.config.DecoderConfig`` under the keys of the published
``config.json``): token ids in, next-token loss out.

Not a flax module: the parameters are a plain dict tree (``embed``,
``layer<i>`` with ``attn_norm q k v gate o mlp_norm`` and ``mlp`` or
``router shared experts``, ``norm``, ``head``; the experts' leaves carry
a leading EXPERT axis) and the surface the engines use is ``init(key,
dummy)``, ``apply({"params": p}, tokens)`` and ``loss(p, tokens, labels,
weights)``.  The worker axis is the engines' ``vmap`` over the stacked
fleet state.

What a layer computes is written out in
``benchmark/reference_models/laguna_xs2.py``'s docstring; this file is
the same mathematics arranged for the chip:

* matmul inputs in the compute dtype (bfloat16 on the chip) with float32
  accumulation; residual stream, norms, router scores, attention
  softmax and the loss in float32;
* attention a block of ``ATTN_BLOCK`` queries at a time against the keys
  that block can see — everything up to its end in a full layer, the
  band of ``sliding_window`` in front of it in a sliding one — so a
  banded layer never forms T x T (``causal_attention``: one entry, and
  the one place that says which of its two bodies runs and why);
* an expert layer that is TOLD which experts it holds
  (``expert_offset``, ``experts_held``), routes every token over all
  ``num_experts`` published ones and adds its own experts' part beside
  the shared expert.  Dispatch is dense and dropless: every held expert
  multiplies every token and a token's combine weight is zero where it
  was not routed, so the result is exact for ANY routing at a fixed
  cost of ``experts_held`` expert passes a token (a grouped matmul that
  skips empty tiles would pay ``num_experts_per_tok * held /
  num_experts`` of them: ROADMAP R3);
* the output head and the loss a block of ``HEAD_BLOCK`` positions at a
  time;
* a layer, an attention block and a head block are ``jax.checkpoint``-ed.
  A layer keeps its matmul products (``LAYER_KEEPS``): the fused attention
  kernel's output, q / k / v as the attention takes them, the per-head
  gate's and the router's products, the residual stream after the output
  projection and every gated MLP's gate and up products, so its backward
  pass recomputes elementwise work and the router's top-k, and no matmul.

Scopes (inside the engines' ``dopt_local``): ``dopt_attn`` (normed input
to gated output projection; the splash kernels carry no name stack and
go by their own names, ``splash_mqa_*``), ``dopt_moe`` (router to combined output)
with ``dopt_route`` inside it (scores, top-k, combine weights and their
application, not the expert matmuls), ``dopt_head`` (final norm, logits,
loss).  ``loss`` also returns the step's routing counts, which the
gossip engine averages into each round's history row.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from dopt.config import DecoderConfig

# What a layer's ``jax.checkpoint`` keeps of its forward pass
# (``LAYER_KEEPS``).  ``ATTN_RESIDUALS``: the fused attention kernel's
# output and log-sum-exp.  ``MATMUL_PRODUCTS``: every value whose
# recompute would be a matmul, in the dtype the forward pass already
# holds it in: q (rotated, cast and, for the kernel, scaled), k and v as
# the attention takes them, the per-head gate's and the router's
# products before their sigmoids, the float32 residual stream after the
# output projection, and the gate and up products of the dense MLP, the
# shared expert and the held experts.  A tag sits on the product itself,
# not past an activation: the backward pass of ``sigmoid`` or ``top_k``
# asks for that primitive's own output, which no name reaches.  At the
# benchmark's cell that is 181-236 MB a layer, worker and row beside the
# kernel's 51-68 (1.01 GB over the five layers, PERF.md, PR 29), and it
# grows with rows x positions x layers a worker.  The recompute keeps
# norms, casts, activations, the gated attention output, the combine
# weights and the router's top-k.
ATTN_RESIDUALS = "attn_residuals"
MATMUL_PRODUCTS = "matmul_products"
LAYER_KEEPS = (ATTN_RESIDUALS, MATMUL_PRODUCTS)
# The routing counts ``loss`` returns beside "acc", in history-row order.
COUNTERS = ("moe_held_slot_share", "moe_load_max_over_mean")
# Queries a block of attention, and positions a block of the output head
# (logits and loss are never held for the whole batch).  Not
# configuration: only tests, whose rows are shorter than a block, pass
# smaller ones to ``GatedMoEDecoder``.
ATTN_BLOCK = 512
HEAD_BLOCK = 1024
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


def _rotary(x, rope):
    """x: [H, T, head_dim]; ``rotate_half`` over the first rotary_dim
    dimensions of each head, float32."""
    inv, scale = _inverse_frequencies(rope, x.shape[-1])
    angles = (jnp.arange(x.shape[-2], dtype=jnp.float32)[:, None]
              * jnp.asarray(inv, jnp.float32)[None, :])
    angles = jnp.concatenate([angles, angles], axis=-1)
    cos, sin = jnp.cos(angles) * scale, jnp.sin(angles) * scale
    rd = angles.shape[-1]
    rot, rest = x[..., :rd], x[..., rd:]
    half = jnp.concatenate([-rot[..., rd // 2:], rot[..., :rd // 2]], -1)
    return jnp.concatenate([rot * cos + half * sin, rest], -1)


@jax.checkpoint
def _attend_block(q, k, v, first_q, first_k, window):
    """Queries at positions ``first_q ...`` against keys at ``first_k
    ...``: q [G, R, Tq, D], k and v [G, Tk, D] -> [G, R, Tq, D].  Scores
    and softmax in float32.  Every query sees at least itself, so no row
    of the mask is empty."""
    scores = jnp.einsum("grqd,gkd->grqk", q, k,
                        preferred_element_type=jnp.float32)
    scores = scores / math.sqrt(q.shape[-1])
    back = ((first_q + jnp.arange(q.shape[-2]))[:, None]
            - (first_k + jnp.arange(k.shape[-2]))[None, :])
    seen = (back >= 0) & (back < window)
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    return jnp.einsum("grqk,gkd->grqd", probs.astype(v.dtype), v)


def blocked_causal_attention(q, k, v, *, window: int | None, block: int):
    """Causal attention of one row, a block of ``block`` queries at a
    time: q [G, R, T, D] (R query heads share each of the G key/value
    heads), k and v [G, T, D].  ``window`` (None = full) is how many
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


def attention_path(t: int, head_dim: int, block: int = ATTN_BLOCK) -> str:
    """Which body ``causal_attention`` runs for a row of ``t`` positions:
    ``"splash"``, the fused kernel, wherever the kernel's own shape
    limits allow it — the row a multiple of twice ``block`` (keys go two
    blocks at a time), ``block`` and the head multiples of the chip's 128
    lanes — else ``"blocked"``.  Nothing else decides it: no option, no
    platform (the kernel is interpreted on the CPU)."""
    fits = t % (2 * block) == 0 and block % 128 == 0 and head_dim % 128 == 0
    return "splash" if fits else "blocked"


def causal_attention(q, k, v, *, window: int | None, block: int = ATTN_BLOCK):
    """Causal (``window`` None) or banded attention of one row: q
    [G * R, T, D] float32 and unscaled (R query heads share each of the
    G key/value heads), k and v [G, T, D] in the compute dtype ->
    [G, R, T, D].

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
    if attention_path(t, d, block) == "splash":
        attend, q = splash_causal_attention, q / math.sqrt(d)
    else:
        attend = blocked_causal_attention
    # kept across a layer's remat as the body takes it: each body's own cast
    q = _keep(q.astype(k.dtype))
    return attend(q.reshape(grouped), k, v, window=window, block=block)


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


def _gated_mlp(p, x, dtype):
    g = _keep(jnp.dot(x, p["gate"].astype(dtype)))
    u = _keep(jnp.dot(x, p["up"].astype(dtype)))
    return jnp.dot(jax.nn.silu(g) * u, p["down"].astype(dtype))


class GatedMoEDecoder:
    """See the module docstring.  ``vocab_rows`` is the slice of the
    vocabulary this worker holds (ids, logits and loss are over it)."""

    counters = COUNTERS

    def __init__(self, cfg: DecoderConfig, *, vocab_rows: int,
                 dtype=jnp.float32, attn_block: int = ATTN_BLOCK,
                 head_block: int = HEAD_BLOCK):
        self.cfg = cfg
        self.vocab_rows = vocab_rows
        self.dtype = jnp.dtype(dtype)
        self.attn_block, self.head_block = attn_block, head_block
        self.experts_held = (cfg.num_experts if cfg.experts_held is None
                             else cfg.experts_held)

    def attention_path(self, t: int) -> str:
        """``"splash"`` or ``"blocked"`` for rows of ``t`` positions
        (``dopt.run`` prints it beside the device)."""
        return attention_path(t, self.cfg.head_dim, self.attn_block)

    # ---------------------------------------------------------- params
    def init(self, key, dummy=None):
        """``{"params": tree}``, float32: normal(0, INITIALIZER_RANGE)
        matrices, unit norm weights.  ``dummy`` is ignored (the engines
        pass a sample for flax models)."""
        c = self.cfg
        d, hd, kv = c.hidden_size, c.head_dim, c.num_key_value_heads
        keys = iter(jax.random.split(key, 16 * c.num_hidden_layers + 2))

        def mat(*shape):
            return INITIALIZER_RANGE * jax.random.normal(
                next(keys), shape, jnp.float32)

        def mlp(width, *lead):
            return {"gate": mat(*lead, d, width), "up": mat(*lead, d, width),
                    "down": mat(*lead, width, d)}

        params = {"embed": mat(self.vocab_rows, d)}
        for i in range(c.num_hidden_layers):
            h = c.num_attention_heads_per_layer[i]
            layer = {"attn_norm": jnp.ones(d), "q": mat(d, h * hd),
                     "k": mat(d, kv * hd), "v": mat(d, kv * hd),
                     "gate": mat(d, h), "o": mat(h * hd, d),
                     "mlp_norm": jnp.ones(d)}
            if c.mlp_layer_types[i] == "dense":
                layer["mlp"] = mlp(c.intermediate_size)
            else:
                layer["router"] = mat(d, c.num_experts)
                layer["shared"] = mlp(c.shared_expert_intermediate_size)
                layer["experts"] = mlp(c.moe_intermediate_size,
                                       self.experts_held)
            params[f"layer{i}"] = layer
        params["norm"] = jnp.ones(d)
        params["head"] = mat(d, self.vocab_rows)
        return {"params": params}

    # ---------------------------------------------------------- layers
    def _attention(self, p, h, i):
        c, dt = self.cfg, self.dtype
        t = h.shape[0]
        hd, kv = c.head_dim, c.num_key_value_heads
        heads = c.num_attention_heads_per_layer[i]
        kind = c.layer_types[i]
        rope = c.rope_parameters[kind]
        with jax.named_scope("dopt_attn"):
            a = _rms(h, p["attn_norm"], c.rms_norm_eps)
            x = a.astype(dt)

            def heads_of(name, n):
                """[n, T, head_dim] float32: head-major, the layout the
                attention wants, straight out of the projection."""
                return jnp.einsum(
                    "td,dne->nte", x, p[name].astype(dt).reshape(-1, n, hd),
                    preferred_element_type=jnp.float32)

            q = _rotary(heads_of("q", heads), rope)
            k = _keep(_rotary(heads_of("k", kv), rope).astype(dt))
            v = _keep(heads_of("v", kv).astype(dt))
            window = (c.sliding_window if kind == "sliding_attention"
                      else None)
            out = causal_attention(q, k, v, window=window,
                                   block=self.attn_block)
            gate = jax.nn.sigmoid(_keep(jnp.einsum(
                "td,dn->nt", x, p["gate"].astype(dt),
                preferred_element_type=jnp.float32)))
            out = out.reshape(heads, t, hd) * gate[..., None].astype(dt)
            return _keep(h + jnp.einsum(
                "nte,ned->td", out, p["o"].astype(dt).reshape(heads, hd, -1),
                preferred_element_type=jnp.float32))

    def _route(self, router, m):
        """[T, held] combine weights (0 where a token was not routed to
        that held expert) and the step's routing counts."""
        c = self.cfg
        scores = jax.nn.sigmoid(_keep(jnp.dot(
            m, router, precision=jax.lax.Precision.HIGHEST)))
        top, idx = jax.lax.top_k(scores, c.num_experts_per_tok)
        top = (top / jnp.sum(top, -1, keepdims=True)
               * c.moe_routed_scaling_factor)
        # [T, k, held]: slot j of token t reached held expert e
        hit = ((idx - c.expert_offset)[..., None]
               == jnp.arange(self.experts_held)).astype(jnp.float32)
        load = hit.sum(axis=(0, 1))                       # [held] slots
        counts = {
            "moe_held_slot_share": load.sum() / idx.size,
            "moe_load_max_over_mean":
                load.max() / jnp.maximum(load.mean(), 1.0 / idx.size),
        }
        return jnp.sum(hit * top[..., None], axis=1), counts

    def _experts(self, p, m):
        dt = self.dtype
        with jax.named_scope("dopt_moe"):
            with jax.named_scope("dopt_route"):
                weight, counts = self._route(p["router"], m)
            x = m.astype(dt)
            out = _gated_mlp(p["shared"], x, dt).astype(jnp.float32)
            e = p["experts"]
            g = _keep(jnp.einsum("td,edf->tef", x, e["gate"].astype(dt)))
            u = _keep(jnp.einsum("td,edf->tef", x, e["up"].astype(dt)))
            mid = jax.nn.silu(g) * u
            with jax.named_scope("dopt_route"):
                mid = mid * weight[..., None].astype(dt)
            out = out + jnp.einsum("tef,efd->td", mid, e["down"].astype(dt),
                                   preferred_element_type=jnp.float32)
            return out, counts

    def _layer(self, p, h, i):
        c = self.cfg
        h = self._attention(p, h, i)
        m = _rms(h, p["mlp_norm"], c.rms_norm_eps)
        if c.mlp_layer_types[i] == "dense":
            return (h + _gated_mlp(p["mlp"], m.astype(self.dtype), self.dtype
                                   ).astype(jnp.float32)), None
        out, counts = self._experts(p, m)
        return h + out, counts

    def _hidden(self, params, tokens):
        """One row: [T] ids -> ([T, d] float32 hidden state before the
        final norm, the routing counts averaged over the expert layers)."""
        h = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
        counts = []
        for i in range(self.cfg.num_hidden_layers):
            h, c = jax.checkpoint(
                lambda p, h_, i=i: self._layer(p, h_, i),
                policy=jax.checkpoint_policies.save_only_these_names(
                    *LAYER_KEEPS))(params[f"layer{i}"], h)
            if c is not None:
                counts.append(c)
        if not counts:
            return h, {k: jnp.zeros(()) for k in COUNTERS}
        return h, {k: jnp.mean(jnp.stack([c[k] for c in counts]))
                   for k in COUNTERS}

    def _logits(self, params, h):
        x = _rms(h, params["norm"], self.cfg.rms_norm_eps).astype(self.dtype)
        return jnp.dot(x, params["head"].astype(self.dtype),
                       preferred_element_type=jnp.float32)

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
        positions, **routing counts})."""
        h, counts = jax.vmap(lambda row: self._hidden(params, row))(tokens)
        counted = (weights[:, None] * (labels >= 0)).astype(jnp.float32)
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

        with jax.named_scope("dopt_head"):
            out = {k: params[k] for k in ("norm", "head")}
            nll, hit = jax.lax.map(
                lambda b: block(out, *b),
                (blocks(h), blocks(labels.astype(jnp.int32)),
                 blocks(counted)))
            total = jnp.maximum(jnp.sum(counted), 1.0)
            aux = {"acc": jnp.sum(hit) / total,
                   **{k: jnp.mean(v) for k, v in counts.items()}}
            return jnp.sum(nll) / total, aux
