"""Plain forward pass and training objective of Laguna-XS.2
(https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json,
``model_type: laguna``), as one chip of a deployment holds it: token ids
in, next-token logits over the held slice of the vocabulary out.

Every size a layer needs that is not a published constant below is read
from the parameters' shapes: the hidden size, the depth (``layer<i>``
keys), layer i's query heads (``q``'s width over ``head_dim``), the
dense, shared and expert widths, the experts held (the ``experts``
leaves' leading axis) and the vocabulary rows.  Layer i's kind follows
the published lists: attention ``full`` where ``i % 4 == 0`` and a
``sliding_window`` band elsewhere, a dense MLP in layer 0 and experts in
every later layer.

Per layer (pre-norm, no biases)::

    a = rms(h) * attn_norm
    q, k, v = a Wq, a Wk, a Wv        # [T, H_l, 128], [T, 8, 128] twice
    q, k = rotary(q), rotary(k)       # by layer kind, below
    p = softmax(q k^T / sqrt(128))    # causal; sliding: 0 <= i - j < 512
    g = sigmoid(a Wg)                 # [T, H_l]: one gate a head
    h = h + concat_h(g_h * (p v)_h) Wo
    m = rms(h) * mlp_norm
    dense:  h = h + (silu(m Wgate) * m Wup) Wdown
    sparse: s = sigmoid(m Wr)         # [T, 256], float32
            top 8 of s a token, w_e = s_e / sum_top(s) * 2.5
            h = h + shared(m) + sum_{e in top, e held} w_e expert_e(m)

``H_l / 8`` query heads share a key/value head.  Rotary embedding,
``rotate_half`` convention over the first ``rotary_dim`` dimensions of a
head: sliding layers all 128 at ``theta = 1e4``; full layers the first
64 (``partial_rotary_factor`` 0.5) with YaRN inverse frequencies
(``theta = 5e5``, ``factor`` 64, ``original_max_position_embeddings``
4096, ``beta_fast`` 64, ``beta_slow`` 1: interpolated and extrapolated
frequencies blended by the linear ramp between the two correction
dimensions) and ``cos``/``sin`` times ``attention_factor``.  What the
absent experts would add is left out, and that partial result goes on
to the next layer.  ``logits = (rms(h) * norm) Whead``; the loss is the
token contract of ``benchmark/reference.py`` alone.

Departures from the published description, each also under ``assumed``
in ``configs/laguna-xs2.json``: the gate is one sigmoid a head from the
normed input (the published parameter count, 33.4B, fits that and not an
element-wise gate); router scores are a sigmoid, the kept weights are
renormalised and then scaled by ``moe_routed_scaling_factor``; no
query/key norm; no balance term in the loss (the config names no
coefficient).

Straightforward ``jax.numpy``, float32, one worker at a time; shares no
code with ``dopt/``.  So that one worker's float32 step fits a chip at
4,096 positions, attention is computed a block of ``Q_BLOCK`` queries at
a time (each block against every key, masked) and a layer and a block
are ``jax.checkpoint``-ed: the arithmetic is that of the unblocked form.
The blocks go through ``jax.lax.map`` and the held experts through one
``einsum`` over their axis, not through python loops: unrolled, the
float32 step compiled to 0.9 GB of code in three minutes, and its cached
executable pushed every other program out of a bounded compile cache
(PERF.md, PR 28).
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import token_cross_entropy

PUBLISHED = {
    "head_dim": 128,
    "kv_heads": 8,
    "eps": 1e-6,
    "window": 512,
    "full_every": 4,              # layer_types: full, sliding x 3, repeated
    "dense_layers": 1,            # mlp_layer_types: dense, then sparse
    "experts": 256,
    "top_k": 8,
    "routed_scaling": 2.5,
    "first_expert": 0,            # this chip holds ids first_expert ...
    "rope_full": {"theta": 500000.0, "partial": 0.5, "factor": 64.0,
                  "original_max": 4096, "beta_fast": 64.0, "beta_slow": 1.0,
                  "attention_factor": 1.4158883083359672},
    "rope_sliding": {"theta": 10000.0, "partial": 1.0},
}
Q_BLOCK = 512


def init(seed: int, spec: dict, *, vocab, dim, heads, dense, expert, held):
    """Seeded parameters for tests (a cell's come from the program's own
    initialiser): ``heads`` is the list of query heads by layer."""
    rng = np.random.default_rng(seed)
    hd, kv = spec["head_dim"], spec["kv_heads"]

    def mat(*shape):
        return (rng.standard_normal(shape) / np.sqrt(shape[-2])
                ).astype(np.float32)

    def norm():
        return (1.0 + 0.1 * rng.standard_normal(dim)).astype(np.float32)

    def mlp(width, *lead):
        return {"gate": mat(*lead, dim, width), "up": mat(*lead, dim, width),
                "down": mat(*lead, width, dim)}

    params = {"embed": mat(vocab, dim) * np.float32(np.sqrt(vocab)),
              "norm": norm(), "head": mat(dim, vocab)}
    for i, h in enumerate(heads):
        layer = {"attn_norm": norm(), "q": mat(dim, h * hd),
                 "k": mat(dim, kv * hd), "v": mat(dim, kv * hd),
                 "gate": mat(dim, h), "o": mat(h * hd, dim),
                 "mlp_norm": norm()}
        if i < spec["dense_layers"]:
            layer["mlp"] = mlp(dense)
        else:
            layer.update(router=mat(dim, spec["experts"]),
                         shared=mlp(expert), experts=mlp(expert, held))
        params[f"layer{i}"] = layer
    return params


def _rms(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


def _inverse_frequencies(rope: dict, head_dim: int):
    """[rotary_dim / 2] inverse frequencies and the cos/sin scale."""
    dim = int(head_dim * rope["partial"])
    pos_freqs = rope["theta"] ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if "factor" not in rope:
        return 1.0 / pos_freqs, 1.0

    def correction_dim(rotations):
        return (dim * math.log(rope["original_max"]
                               / (rotations * 2 * math.pi))
                / (2 * math.log(rope["theta"])))

    low = max(math.floor(correction_dim(rope["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rope["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    extrapolated = 1.0 - ramp             # 1 where the frequency is kept
    inv = (1.0 / (rope["factor"] * pos_freqs) * (1.0 - extrapolated)
           + 1.0 / pos_freqs * extrapolated)
    return inv, rope["attention_factor"]


def _rotary(x, rope: dict):
    """x: [T, H, head_dim]; rotates the first rotary_dim of each head."""
    t, _, hd = x.shape
    inv, scale = _inverse_frequencies(rope, hd)
    angles = np.arange(t, dtype=np.float64)[:, None] * inv[None, :]
    angles = np.concatenate([angles, angles], axis=-1)      # [T, rotary_dim]
    cos = jnp.asarray(np.cos(angles) * scale, jnp.float32)[:, None, :]
    sin = jnp.asarray(np.sin(angles) * scale, jnp.float32)[:, None, :]
    rd = angles.shape[-1]
    rot, rest = x[..., :rd], x[..., rd:]
    half = jnp.concatenate([-rot[..., rd // 2:], rot[..., :rd // 2]], -1)
    return jnp.concatenate([rot * cos + half * sin, rest], -1)


@jax.checkpoint
def _attend(q, k, v, first, window):
    """One block of queries (positions ``first`` ...) against every key,
    masked: q [Tq, G, R, D], k and v [T, G, D] -> [Tq, G, R, D]."""
    scores = jnp.einsum("qgrd,kgd->grqk", q, k) / math.sqrt(q.shape[-1])
    back = (first + jnp.arange(q.shape[0]))[:, None] - jnp.arange(k.shape[0])
    seen = (back >= 0) & (back < window)
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    return jnp.einsum("grqk,kgd->qgrd", probs, v)


def _attention(p, h, window, rope, spec):
    t = h.shape[0]
    hd, kv = spec["head_dim"], spec["kv_heads"]
    a = _rms(h, p["attn_norm"], spec["eps"])
    heads = p["q"].shape[1] // hd
    q = _rotary((a @ p["q"]).reshape(t, heads, hd), rope)
    k = _rotary((a @ p["k"]).reshape(t, kv, hd), rope)
    v = (a @ p["v"]).reshape(t, kv, hd)
    # Blocks of Q_BLOCK queries (a row that is no multiple of it: one
    # block), one after the other through ONE piece of code.
    block = Q_BLOCK if t % Q_BLOCK == 0 else t
    out = jax.lax.map(
        lambda b: _attend(b[0], k, v, b[1], window or t),
        (q.reshape(t // block, block, kv, heads // kv, hd),
         block * jnp.arange(t // block)))
    gate = jax.nn.sigmoid(a @ p["gate"])                    # [T, heads]
    out = out.reshape(t, heads, hd) * gate[..., None]
    return h + out.reshape(t, heads * hd) @ p["o"]


def _gated(p, m):
    return (jax.nn.silu(m @ p["gate"]) * (m @ p["up"])) @ p["down"]


def _experts(p, m, spec):
    scores = jax.nn.sigmoid(m @ p["router"])                # [T, E_pub]
    top, idx = jax.lax.top_k(scores, spec["top_k"])
    top = top / jnp.sum(top, -1, keepdims=True) * spec["routed_scaling"]
    # [T, E_pub]: a token's weight on each published expert, 0 if not routed
    weight = jnp.sum(jax.nn.one_hot(idx, spec["experts"]) * top[..., None], -2)
    e = p["experts"]                 # [held, ...]: the absent add nothing
    held = e["gate"].shape[0]
    weight = weight[:, spec["first_expert"]:spec["first_expert"] + held]
    mid = (jax.nn.silu(jnp.einsum("td,edf->tef", m, e["gate"]))
           * jnp.einsum("td,edf->tef", m, e["up"]))
    routed = jnp.einsum("tef,efd->ted", mid, e["down"])     # expert_e(m)
    return _gated(p["shared"], m) + jnp.sum(weight[..., None] * routed, 1)


def _layer(p, h, i, spec):
    full = i % spec["full_every"] == 0
    h = _attention(p, h, None if full else spec["window"],
                   spec["rope_full" if full else "rope_sliding"], spec)
    m = _rms(h, p["mlp_norm"], spec["eps"])
    if i < spec["dense_layers"]:
        return h + _gated(p["mlp"], m)
    return h + _experts(p, m, spec)


def _row(params, tokens, spec):
    """[T] ids -> [T, V] logits."""
    h = params["embed"][tokens]
    for i in range(sum(k.startswith("layer") for k in params)):
        h = jax.checkpoint(lambda p, h_, i=i: _layer(p, h_, i, spec))(
            params[f"layer{i}"], h)
    return _rms(h, params["norm"], spec["eps"]) @ params["head"]


def forward(params, x, spec=PUBLISHED):
    """[B, T] int32 token ids -> [B, T, V] logits."""
    return jnp.stack([_row(params, row, spec) for row in x])


def objective(params, x, y, w, spec=PUBLISHED):
    return token_cross_entropy(forward(params, x, spec), y, w)
