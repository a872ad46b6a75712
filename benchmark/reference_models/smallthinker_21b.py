"""Plain forward pass and training objective of SmallThinker-21BA3B
(https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct/blob/main/config.json,
``model_name: smallthinker_21b_instruct``), as one chip of a deployment
holds it: token ids in, next-token logits over the held slice of the
vocabulary out.

Every size a layer needs that is not a published constant below is read
from the parameters' shapes: the hidden size, the depth (``layer<i>``
keys), the query heads (``q``'s width over ``head_dim``), the expert
width, the experts held (the ``experts`` leaves' leading axis) and the
vocabulary rows.  Layer i's kind follows the published
``rope_layout`` and ``sliding_window_layout`` (both ``[0, 1, 1, 1]``
repeated): where ``i % 4 == 0`` full causal attention with NoPE (no
rotary at all), elsewhere rotary attention in a band of ``window``
positions.  Every layer has experts and none has a shared one.

Per layer (pre-norm, no biases)::

    a = rms(h) * attn_norm
    s = softmax(a Wr)                 # [T, 64], float32: BEFORE attention
    top 6 of s a token, w_e = s_e / sum_top(s)
    q, k, v = a Wq, a Wk, a Wv        # [T, 28, 128], [T, 4, 128] twice
    rotary layers: q, k = rotary(q), rotary(k)
    p = softmax(q k^T / sqrt(128))    # causal; banded: 0 <= i - j < 4096
    h = h + concat_h((p v)_h) Wo
    m = rms(h) * mlp_norm
    h = h + sum_{e in top, e held} w_e (relu(m Wgate_e) * m Wup_e) Wdown_e

7 query heads share a key/value head.  Rotary embedding:
``rotate_half`` over the whole head at ``theta = 1.5e6``, no scaling.
What the absent experts would add is left out, and that partial result
goes on to the next layer.  ``logits = (rms(h) * norm) Whead``; the loss
is the token contract of ``benchmark/reference.py`` alone.

Departures from the published description, each also under ``assumed``
in ``configs/smallthinker-21b-a3b.json``: the router reads the
attention's normed input ``a`` (the description places it "before
attention"; the config names no norm of its own); its weights are the
softmax over all 64 logits taken at the chosen 6 and renormalised, with
no routed scale; the experts are ReGLU (``relu`` gate, the description's
"sparse ReGLU"); the "secondary experts" of the description are not
built (the config has keys for primary experts only); no query/key norm
and no balance term in the loss (the config names neither).

Straightforward ``jax.numpy``, float32, one worker at a time; shares no
code with ``dopt/``.  So that one worker's float32 step fits a chip at
8,192 positions, attention is computed a block of ``Q_BLOCK`` queries at
a time (each block against every key, masked), the combine weight goes
into the expert's product before its down projection (no [T, held,
hidden] array), and a layer and a block are ``jax.checkpoint``-ed: the
arithmetic is that of the unblocked form.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import token_cross_entropy

PUBLISHED = {
    "head_dim": 128,
    "kv_heads": 4,
    "eps": 1e-6,
    "window": 4096,
    "full_every": 4,              # layouts: NoPE full, rotary banded x 3
    "experts": 64,
    "top_k": 6,
    "first_expert": 0,            # this chip holds ids first_expert ...
    "rope_theta": 1500000.0,
}
Q_BLOCK = 512


def init(seed: int, spec: dict, *, vocab, dim, heads, layers, expert, held):
    """Seeded parameters for tests (a cell's come from the program's own
    initialiser): ``heads`` query heads in each of ``layers`` layers."""
    rng = np.random.default_rng(seed)
    hd, kv = spec["head_dim"], spec["kv_heads"]

    def mat(*shape):
        return (rng.standard_normal(shape) / np.sqrt(shape[-2])
                ).astype(np.float32)

    def norm():
        return (1.0 + 0.1 * rng.standard_normal(dim)).astype(np.float32)

    params = {"embed": mat(vocab, dim) * np.float32(np.sqrt(vocab)),
              "norm": norm(), "head": mat(dim, vocab)}
    for i in range(layers):
        params[f"layer{i}"] = {
            "attn_norm": norm(), "q": mat(dim, heads * hd),
            "k": mat(dim, kv * hd), "v": mat(dim, kv * hd),
            "o": mat(heads * hd, dim), "mlp_norm": norm(),
            "router": mat(dim, spec["experts"]),
            "experts": {"gate": mat(held, dim, expert),
                        "up": mat(held, dim, expert),
                        "down": mat(held, expert, dim)}}
    return params


def _rms(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


def _rotary(x, theta):
    """x: [T, H, head_dim]; rotates the whole head (``rotate_half``)."""
    t, _, hd = x.shape
    inv = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    angles = np.arange(t, dtype=np.float64)[:, None] * inv[None, :]
    angles = np.concatenate([angles, angles], axis=-1)      # [T, head_dim]
    cos = jnp.asarray(np.cos(angles), jnp.float32)[:, None, :]
    sin = jnp.asarray(np.sin(angles), jnp.float32)[:, None, :]
    half = jnp.concatenate([-x[..., hd // 2:], x[..., :hd // 2]], -1)
    return x * cos + half * sin


@jax.checkpoint
def _attend(q, k, v, first, window):
    """One block of queries (positions ``first`` ...) against every key,
    masked: q [Tq, G, R, D], k and v [T, G, D] -> [Tq, G, R, D]."""
    scores = jnp.einsum("qgrd,kgd->grqk", q, k) / math.sqrt(q.shape[-1])
    back = (first + jnp.arange(q.shape[0]))[:, None] - jnp.arange(k.shape[0])
    seen = (back >= 0) & (back < window)
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    return jnp.einsum("grqk,kgd->qgrd", probs, v)


def _attention(p, h, a, window, rotary, spec):
    t = h.shape[0]
    hd, kv = spec["head_dim"], spec["kv_heads"]
    heads = p["q"].shape[1] // hd
    q = (a @ p["q"]).reshape(t, heads, hd)
    k = (a @ p["k"]).reshape(t, kv, hd)
    if rotary:
        q, k = _rotary(q, spec["rope_theta"]), _rotary(k, spec["rope_theta"])
    v = (a @ p["v"]).reshape(t, kv, hd)
    # Blocks of Q_BLOCK queries (a row that is no multiple of it: one
    # block), one after the other through ONE piece of code.
    block = Q_BLOCK if t % Q_BLOCK == 0 else t
    out = jax.lax.map(
        lambda b: _attend(b[0], k, v, b[1], window or t),
        (q.reshape(t // block, block, kv, heads // kv, hd),
         block * jnp.arange(t // block)))
    return h + out.reshape(t, heads * hd) @ p["o"]


def _route(p, a, spec):
    """[T, held] combine weights of the held experts, from the router's
    input ``a``: softmax over the published experts, top k, renormalised,
    0 where a token was not routed to the expert."""
    scores = jax.nn.softmax(a @ p["router"], axis=-1)       # [T, E_pub]
    top, idx = jax.lax.top_k(scores, spec["top_k"])
    top = top / jnp.sum(top, -1, keepdims=True)
    weight = jnp.sum(jax.nn.one_hot(idx, spec["experts"]) * top[..., None], -2)
    held = p["experts"]["gate"].shape[0]
    return weight[:, spec["first_expert"]:spec["first_expert"] + held]


def _experts(p, m, weight):
    """sum over the held experts e of weight_e * ReGLU_e(m)."""
    e = p["experts"]                 # [held, ...]: the absent add nothing
    mid = (jax.nn.relu(jnp.einsum("td,edf->tef", m, e["gate"]))
           * jnp.einsum("td,edf->tef", m, e["up"]))
    return jnp.einsum("tef,efd->td", mid * weight[..., None], e["down"])


def _layer(p, h, i, spec):
    full = i % spec["full_every"] == 0
    a = _rms(h, p["attn_norm"], spec["eps"])
    weight = _route(p, a, spec)                  # before the attention
    h = _attention(p, h, a, None if full else spec["window"], not full, spec)
    m = _rms(h, p["mlp_norm"], spec["eps"])
    return h + _experts(p, m, weight)


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
