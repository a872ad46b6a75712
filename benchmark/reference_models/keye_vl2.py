"""Plain forward pass and training objective of the language model of
Keye-VL-2.0-30B-A3B
(https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/config.json,
``model_type: KeyeVL2``), as one chip of a deployment holds it: token ids
in, next-token logits over the held slice of the vocabulary out.  The
vision tower is left out: the training path takes token rows.

Every size a layer needs that is not a published constant below is read
from the parameters' shapes: the hidden size, the depth (``layer<i>``
keys), the query heads (``q``'s width over ``head_dim``), the expert
width, the experts held (the ``experts`` leaves' leading axis) and the
vocabulary rows.  All layers are alike (``decoder_sparse_step`` 1 and
``mlp_only_layers`` [] make every MLP sparse).

Per layer (pre-norm, no biases on any matrix)::

    a  = rms(h) * attn_norm
    q, k, v = a Wq, a Wk, a Wv          # [T, 32, 128], [T, 4, 128] twice
    q, k = rms_head(q) * q_norm, rms_head(k) * k_norm     # over a head's 128
    q, k = rotary(q), rotary(k)         # theta 1e7, all 128 dims
    A  = stop_gradient(a)               # the indexer's input is detached
    qI = rotary(A WqI)                  # [T, 16, 64]
    kI = rotary(layernorm(A WkI))       # [T, 64]: ONE key head
    wI = (A Ww) * 16^-1/2 * 64^-1/2     # [T, 16]
    I[t, s] = sum_j wI[t, j] * relu(qI[t, j] . kI[s]),      s <= t
    S_t = the min(t + 1, 2048) positions s <= t with the largest I[t, s]
          (ties: the lower s)
    P_h[t, s] = softmax over s in S_t of q_h[t] . k_g(h)[s] / sqrt(128)
    h  = h + concat_h(P_h v_g(h)) Wo    # no output gate
    align = mean_t sum_{s in S_t} p[t, s] * (log p[t, s]
                                    - log_softmax_{S_t}(I[t, .])[s]),
            p[t, s] = stop_gradient(mean_h P_h[t, s])
    m  = rms(h) * mlp_norm
    s  = softmax(m Wr)                  # [T, 128], float32
    top 8 of s a token, w_e = s_e / sum_top(s)
    h  = h + sum_{e in top, e held} w_e expert_e(m)   # no shared expert

8 query heads share a key/value head.  ``logits = (rms(h) * norm)
Whead``; the training loss is the token contract of
``benchmark/reference.py`` plus the sum over layers of ``align``, each a
mean over the positions of the rows that count.  The selection is not
differentiated, so the model's leaves get the cross-entropy's gradient
alone and the indexer's (``WqI``, ``WkI``, its layer norm, ``Ww``) the
alignment term's alone: the sparse training stage of the DeepSeek-V3.2-Exp
report, whose lightning indexer this is.  What the absent experts would
add is left out, and that partial result goes on to the next layer.

Departures from and readings of the published description, each also
under ``assumed`` in ``configs/keye-vl2-30b-a3b.json``: per-head RMS
norms on q and k (the config's keys are Qwen3-MoE's, which has them and
no key for them); the indexer's layer norm (weight and bias, eps 1e-6)
on its key, its rotary over all 64 dimensions at the model's theta and
the scale of ``wI``, as in the DSA reference code; ``q_chunk_size`` /
``kv_chunk_size`` read as the tiling of the indexer's computation, with
no effect on the result; the alignment term's coefficient 1;
``mrope_section`` collapsing to the plain rotary on text rows, whose
three position components are equal; no router balance term.

Straightforward ``jax.numpy``, float32, one worker at a time; shares no
code with ``dopt/``.  So that one worker's float32 step fits a chip at
8,192 positions, attention is computed a block of ``Q_BLOCK`` queries at
a time (each block against every key, masked; the selection by a stable
sort of the whole row of index scores) and a layer and a block are
``jax.checkpoint``-ed: the arithmetic is that of the unblocked form.
The blocks go through ``jax.lax.map``, the held experts through one
``einsum`` over their axis (``laguna_xs2.py`` says why).
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
    "theta": 10000000.0,
    "experts": 128,
    "top_k": 8,
    "first_expert": 0,            # this chip holds ids first_expert ...
    "index_heads": 16,
    "index_dim": 64,
    "index_top": 2048,
}
Q_BLOCK = 512


def init(seed: int, spec: dict, *, vocab, dim, heads, layers, expert, held):
    """Seeded parameters for tests (a cell's come from the program's own
    initialiser): ``layers`` layers of ``heads`` query heads."""
    rng = np.random.default_rng(seed)
    hd, kv = spec["head_dim"], spec["kv_heads"]
    j, e = spec["index_heads"], spec["index_dim"]

    def mat(*shape):
        return (rng.standard_normal(shape) / np.sqrt(shape[-2])
                ).astype(np.float32)

    def norm(width):
        return (1.0 + 0.1 * rng.standard_normal(width)).astype(np.float32)

    params = {"embed": mat(vocab, dim) * np.float32(np.sqrt(vocab)),
              "norm": norm(dim), "head": mat(dim, vocab)}
    for i in range(layers):
        params[f"layer{i}"] = {
            "attn_norm": norm(dim), "q": mat(dim, heads * hd),
            "k": mat(dim, kv * hd), "v": mat(dim, kv * hd),
            "q_norm": norm(hd), "k_norm": norm(hd),
            "o": mat(heads * hd, dim), "mlp_norm": norm(dim),
            "indexer": {"q": mat(dim, j * e), "k": mat(dim, e),
                        "k_norm": norm(e), "k_bias": norm(e) - 1.0,
                        "w": mat(dim, j)},
            "router": mat(dim, spec["experts"]),
            "experts": {"gate": mat(held, dim, expert),
                        "up": mat(held, dim, expert),
                        "down": mat(held, expert, dim)}}
    return params


def _rms(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


def _layernorm(x, weight, bias, eps):
    x = x - jnp.mean(x, -1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * weight + bias


def _rotary(x, theta):
    """x: [T, H, D]; ``rotate_half`` over all D dimensions of each head."""
    t, _, d = x.shape
    inv = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    angles = np.arange(t, dtype=np.float64)[:, None] * inv[None, :]
    angles = np.concatenate([angles, angles], axis=-1)            # [T, D]
    cos = jnp.asarray(np.cos(angles), jnp.float32)[:, None, :]
    sin = jnp.asarray(np.sin(angles), jnp.float32)[:, None, :]
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + half * sin


def select(index, seen, top):
    """[Tq, T] bool: for query row t (a row of ``seen`` marks the
    positions s <= t) its min(t + 1, top) visible positions with the
    largest index score, ties to the lower position: the rank of every
    position in a stable descending sort of the row."""
    order = jnp.argsort(-jnp.where(seen, index, -jnp.inf), axis=-1,
                        stable=True)
    rank = jnp.argsort(order, axis=-1)
    return seen & (rank < top)


@jax.checkpoint
def _attend(q, k, v, qi, ki, wi, first, top):
    """One block of queries (positions ``first`` ...) against every key:
    q [Tq, G, R, D], k and v [T, G, D], qi [Tq, J, E], ki [T, E], wi
    [Tq, J] -> ([Tq, G, R, D], the block's alignment sum)."""
    at = first + jnp.arange(q.shape[0])
    seen = jnp.arange(k.shape[0])[None, :] <= at[:, None]
    index = jnp.einsum("qj,qjk->qk", wi,
                       jax.nn.relu(jnp.einsum("qje,ke->qjk", qi, ki)))
    chosen = select(jax.lax.stop_gradient(index), seen, top)
    scores = jnp.einsum("qgrd,kgd->grqk", q, k) / math.sqrt(q.shape[-1])
    probs = jax.nn.softmax(jnp.where(chosen, scores, -jnp.inf), axis=-1)
    p = jax.lax.stop_gradient(jnp.mean(probs, axis=(0, 1)))       # [Tq, T]
    logq = jax.nn.log_softmax(jnp.where(chosen, index, -jnp.inf), axis=-1)
    kl = jnp.where(chosen, p * (jnp.log(jnp.where(p > 0, p, 1.0)) - logq),
                   0.0)
    return jnp.einsum("grqk,kgd->qgrd", probs, v), jnp.sum(kl)


def _attention(p, h, spec):
    """-> (the residual stream after the attention, the row's alignment
    term)."""
    t = h.shape[0]
    hd, kv = spec["head_dim"], spec["kv_heads"]
    j, e = spec["index_heads"], spec["index_dim"]
    a = _rms(h, p["attn_norm"], spec["eps"])
    heads = p["q"].shape[1] // hd
    q = _rms((a @ p["q"]).reshape(t, heads, hd), p["q_norm"], spec["eps"])
    k = _rms((a @ p["k"]).reshape(t, kv, hd), p["k_norm"], spec["eps"])
    q, k = _rotary(q, spec["theta"]), _rotary(k, spec["theta"])
    v = (a @ p["v"]).reshape(t, kv, hd)
    ix = p["indexer"]
    detached = jax.lax.stop_gradient(a)
    qi = _rotary((detached @ ix["q"]).reshape(t, j, e), spec["theta"])
    ki = _rotary(_layernorm(detached @ ix["k"], ix["k_norm"], ix["k_bias"],
                            spec["eps"])[:, None, :], spec["theta"])[:, 0]
    wi = (detached @ ix["w"]) * (j ** -0.5 * e ** -0.5)
    block = Q_BLOCK if t % Q_BLOCK == 0 else t
    out, kl = jax.lax.map(
        lambda b: _attend(b[0], k, v, b[1], ki, b[2], b[3],
                          spec["index_top"]),
        (q.reshape(t // block, block, kv, heads // kv, hd),
         qi.reshape(t // block, block, j, e),
         wi.reshape(t // block, block, j),
         block * jnp.arange(t // block)))
    return h + out.reshape(t, heads * hd) @ p["o"], jnp.sum(kl) / t


def _experts(p, m, spec):
    scores = jax.nn.softmax(m @ p["router"], axis=-1)       # [T, E_pub]
    top, idx = jax.lax.top_k(scores, spec["top_k"])
    top = top / jnp.sum(top, -1, keepdims=True)
    # [T, E_pub]: a token's weight on each published expert, 0 if not routed
    weight = jnp.sum(jax.nn.one_hot(idx, spec["experts"]) * top[..., None], -2)
    e = p["experts"]                 # [held, ...]: the absent add nothing
    held = e["gate"].shape[0]
    weight = weight[:, spec["first_expert"]:spec["first_expert"] + held]
    mid = (jax.nn.silu(jnp.einsum("td,edf->tef", m, e["gate"]))
           * jnp.einsum("td,edf->tef", m, e["up"]))
    routed = jnp.einsum("tef,efd->ted", mid, e["down"])     # expert_e(m)
    return jnp.sum(weight[..., None] * routed, 1)


def _layer(p, h, spec):
    h, align = _attention(p, h, spec)
    m = _rms(h, p["mlp_norm"], spec["eps"])
    return h + _experts(p, m, spec), align


def _row(params, tokens, spec):
    """[T] ids -> ([T, V] logits, the sum over layers of the row's
    alignment terms)."""
    h = params["embed"][tokens]
    aligns = []
    for i in range(sum(k.startswith("layer") for k in params)):
        h, align = jax.checkpoint(lambda p, h_: _layer(p, h_, spec))(
            params[f"layer{i}"], h)
        aligns.append(align)
    return _rms(h, params["norm"], spec["eps"]) @ params["head"], sum(aligns)


def forward(params, x, spec=PUBLISHED):
    """[B, T] int32 token ids -> [B, T, V] logits."""
    return jnp.stack([_row(params, row, spec)[0] for row in x])


def objective(params, x, y, w, spec=PUBLISHED):
    """The token contract's cross-entropy plus the layers' alignment
    terms, a mean over the rows that count (``w`` 0 / 1 a row)."""
    rows = [_row(params, row, spec) for row in x]
    align = jnp.stack([a for _, a in rows])
    return (token_cross_entropy(jnp.stack([r for r, _ in rows]), y, w)
            + jnp.sum(w * align) / jnp.maximum(jnp.sum(w), 1.0))
