"""Plain forward pass and training objective of a TOY decoder (no
published model: widths chosen for the tests), the shape of file a
sequence configuration brings: token ids in, next-token logits out.

Two pre-norm decoder layers over an embedding table and an untied
output head, RMS norms, rotary-free causal attention with grouped
key/value heads (``heads`` query heads share ``kv_heads``):

* layer 0: attention over a sliding window of ``window`` positions, then
  a dense gated MLP (``silu(x Wg) * (x Wu)) Wd``);
* layer 1: full causal attention, then a layer of experts: a router of
  ``experts_published`` outputs picks ``experts_per_token`` a token by
  softmax score; this chip HOLDS the first ``E`` of them (the ``experts``
  leaves' leading axis) and adds only their part of the result, weighted
  by the renormalised scores, beside one shared expert every token
  passes through.

``objective`` is the token contract of ``benchmark/reference.py`` plus
the router's load-balance term (Switch-style: ``E_pub * sum_e f_e P_e``,
``f_e`` the share of routed slots expert e got, ``P_e`` its mean score)
at ``AUX_COEF``.

Straightforward ``jax.numpy``, float32, one worker at a time; shares no
code with ``dopt/``.
"""

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import token_cross_entropy

HEADS, KV_HEADS, HEAD_DIM, WINDOW = 4, 2, 8, 4
EXPERTS_PUBLISHED, EXPERTS_PER_TOKEN = 8, 2
AUX_COEF = 0.01


def init(seed: int, *, vocab=48, dim=32, mlp=64, held=4, expert=16):
    """Seeded parameters at the toy widths (for tests: a cell's come from
    the program's own initialiser)."""
    rng = np.random.default_rng(seed)

    def mat(*shape):
        return (rng.standard_normal(shape) / np.sqrt(shape[-2])
                ).astype(np.float32)

    def attn():
        return {"norm": np.ones(dim, np.float32),
                "q": mat(dim, HEADS * HEAD_DIM),
                "k": mat(dim, KV_HEADS * HEAD_DIM),
                "v": mat(dim, KV_HEADS * HEAD_DIM),
                "o": mat(HEADS * HEAD_DIM, dim)}

    def mlp_(n, *lead):
        return {"gate": mat(*lead, dim, n), "up": mat(*lead, dim, n),
                "down": mat(*lead, n, dim)}

    return {
        "embed": mat(vocab, dim) * np.float32(np.sqrt(vocab)),
        "layer0": {"attn": attn(), "norm": np.ones(dim, np.float32),
                   "mlp": mlp_(mlp)},
        "layer1": {"attn": attn(), "norm": np.ones(dim, np.float32),
                   "router": mat(dim, EXPERTS_PUBLISHED),
                   "experts": mlp_(expert, held), "shared": mlp_(expert)},
        "norm": np.ones(dim, np.float32),
        "head": mat(dim, vocab),
    }


def _rms(x, weight):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * weight


def _attention(p, x, window):
    b, t, _ = x.shape
    h = _rms(x, p["norm"])
    q = (h @ p["q"]).reshape(b, t, KV_HEADS, HEADS // KV_HEADS, HEAD_DIM)
    k = (h @ p["k"]).reshape(b, t, KV_HEADS, HEAD_DIM)
    v = (h @ p["v"]).reshape(b, t, KV_HEADS, HEAD_DIM)
    scores = jnp.einsum("bqgrd,bkgd->bgrqk", q, k) / np.sqrt(HEAD_DIM)
    pos = jnp.arange(t)
    back = pos[:, None] - pos[None, :]              # query - key
    seen = (back >= 0) & (back < (window or t))
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("bgrqk,bkgd->bqgrd", probs, v)
    return x + out.reshape(b, t, HEADS * HEAD_DIM) @ p["o"]


def _gated(p, h):
    return (jax.nn.silu(h @ p["gate"]) * (h @ p["up"])) @ p["down"]


def _experts(p, x):
    """Returns the layer's output and the router's balance term."""
    h = _rms(x, p["norm"])
    scores = jax.nn.softmax(h @ p["router"], axis=-1)          # [B, T, E_pub]
    top, idx = jax.lax.top_k(scores, EXPERTS_PER_TOKEN)
    top = top / jnp.sum(top, -1, keepdims=True)
    # [B, T, E_pub]: a token's weight on each published expert, 0 if not routed
    weight = jnp.sum(jax.nn.one_hot(idx, EXPERTS_PUBLISHED) * top[..., None], -2)
    held = p["experts"]["gate"].shape[0]
    out = _gated(p["shared"], h)
    for e in range(held):                     # the absent experts add nothing
        one = jax.tree.map(lambda a: a[e], p["experts"])
        out = out + weight[..., e:e + 1] * _gated(one, h)
    routed = jnp.mean(jnp.sum(jax.nn.one_hot(idx, EXPERTS_PUBLISHED), -2),
                      axis=(0, 1)) / EXPERTS_PER_TOKEN
    balance = EXPERTS_PUBLISHED * jnp.sum(
        jax.lax.stop_gradient(routed) * jnp.mean(scores, axis=(0, 1)))
    return x + out, balance


def _logits(params, x):
    h = params["embed"][x]
    h = _attention(params["layer0"]["attn"], h, WINDOW)
    h = h + _gated(params["layer0"]["mlp"], _rms(h, params["layer0"]["norm"]))
    h = _attention(params["layer1"]["attn"], h, None)
    h, balance = _experts(params["layer1"], h)
    return _rms(h, params["norm"]) @ params["head"], balance


def forward(params, x):
    """[B, T] int32 token ids -> [B, T, V] logits."""
    return _logits(params, x)[0]


def objective(params, x, y, w):
    logits, balance = _logits(params, x)
    return token_cross_entropy(logits, y, w) + AUX_COEF * balance
