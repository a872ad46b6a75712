"""Plain forward pass and training objective of JoyAI-LLM-Flash
(https://huggingface.co/jdopensource/JoyAI-LLM-Flash/blob/main/config.json,
``model_type: joyai_llm_flash``, 48B-A2.7B, DeepSeek-V3's architecture:
arXiv:2412.19437), as one chip of a deployment holds it: token ids in,
next-token logits over the held slice of the vocabulary out.

Every size that is not a published constant below is read from the
parameters' shapes: the hidden size, the depth (``layer<i>`` keys), the
heads (``kv_b``'s width over ``nope + v``), the two latent ranks, the
dense, shared and expert widths, the experts held (the ``experts``
leaves' leading axis) and the vocabulary rows.  Layers
``0 .. dense_layers - 1`` have a dense MLP, every later one experts.

Per layer (pre-norm, no biases), with ``rms`` the RMS norm::

    a = rms(h) * attn_norm
    c_q = rms(a Wqa) * q_a_norm                     # [T, q_lora_rank]
    [q_nope | q_pe] = c_q Wqb                       # [T, H, 128 + 64]
    [c_kv | k_pe] = a Wkva                          # [T, 512 + 64]
    [k_nope | v] = (rms(c_kv) * kv_a_norm) Wkvb     # [T, H, 128 + 128]
    q = [q_nope | rot(q_pe)], k = [k_nope | rot(k_pe)]   # k_pe: ONE for all H
    p = softmax(q k^T / sqrt(192))                  # causal
    h = h + concat_h(p v) Wo
    m = rms(h) * mlp_norm
    dense:  h = h + (silu(m Wgate) * m Wup) Wdown
    sparse: s = sigmoid(m Wr)                       # [T, 256], float32
            top 8 of s + b a token (b: the correction bias),
            w_e = s_e / sum_top(s) * 2.5
            h = h + shared(m) + sum_{e in top, e held} w_e expert_e(m)

``rot`` is the rotary embedding over the 64 dimensions in INTERLEAVED
pairs (``rope_interleave``): dimensions 2i and 2i + 1 turn by the angle
``t * theta^(-2i / 64)`` (theta 3.2e7, no scaling).  What the absent
experts would add is left out, and that partial result goes on to the
next layer.  ``logits = (rms(h) * norm) Whead``.

The multi-token-prediction module (``num_nextn_predict_layers`` 1,
DeepSeek-V3 section 2.2, ``mtp``): with ``h_i`` the last layer's output
before the final norm and ``t_{i+1}`` the next token,
``h'_i = [rms(Emb(t_{i+1})) * enorm ; rms(h_i) * hnorm] Weh``, one more
layer with experts, then ``(rms(.) * mtp.norm) Whead`` with the SHARED
embedding and head, against ``t_{i+2}``.  The objective is the token
contract of ``benchmark/reference.py`` on the main logits plus
``mtp_weight`` times the same contract on the module's logits, a
position counted where its label and its next label are.

Departures from the published description, each also under ``assumed``
in ``configs/joyai-llm-flash.json``: ``mtp_weight`` 0.3 (DeepSeek-V3
section 4.2's value for the first 10 T tokens; the config gives none);
the correction bias is a parameter the objective does not train (its
load-based update is not run: its speed is not published); no
sequence-wise balance loss (no coefficient is published); the embedding
rows of the halves in ``Weh``'s input in the checkpoints' order (the
paper writes them the other way round, a permutation of ``Weh``'s rows).

Straightforward ``jax.numpy``, float32, one worker at a time; shares no
code with ``dopt/``.  So that one worker's float32 step fits a chip at
4,096 positions, attention is computed a block of ``Q_BLOCK`` queries at
a time (each block against every key, masked) and a layer and a block
are ``jax.checkpoint``-ed: the arithmetic is that of the unblocked form.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import token_cross_entropy

PUBLISHED = {
    "nope": 128,                  # qk_nope_head_dim
    "rope": 64,                   # qk_rope_head_dim
    "v": 128,                     # v_head_dim
    "eps": 1e-6,
    "theta": 32000000.0,
    "dense_layers": 1,            # first_k_dense_replace
    "experts": 256,
    "top_k": 8,
    "routed_scaling": 2.5,
    "first_expert": 0,            # this chip holds ids first_expert ...
    "mtp_weight": 0.3,
}
Q_BLOCK = 512


def init(seed: int, spec: dict, *, vocab, dim, heads, layers, dense, expert,
         held, q_rank, kv_rank, bias_scale=0.0):
    """Seeded parameters for tests (a cell's come from the program's own
    initialiser), with a multi-token-prediction module; the correction
    bias is drawn at ``bias_scale`` (0: the published initial value)."""
    rng = np.random.default_rng(seed)
    qk = spec["nope"] + spec["rope"]

    def mat(*shape):
        return (rng.standard_normal(shape) / np.sqrt(shape[-2])
                ).astype(np.float32)

    def norm(n=dim):
        return (1.0 + 0.1 * rng.standard_normal(n)).astype(np.float32)

    def mlp(width, *lead):
        return {"gate": mat(*lead, dim, width), "up": mat(*lead, dim, width),
                "down": mat(*lead, width, dim)}

    def layer(sparse):
        p = {"attn_norm": norm(), "q_a": mat(dim, q_rank),
             "q_a_norm": norm(q_rank), "q_b": mat(q_rank, heads * qk),
             "kv_a": mat(dim, kv_rank + spec["rope"]),
             "kv_a_norm": norm(kv_rank),
             "kv_b": mat(kv_rank, heads * (spec["nope"] + spec["v"])),
             "o": mat(heads * spec["v"], dim), "mlp_norm": norm()}
        if sparse:
            p.update(router=mat(dim, spec["experts"]),
                     router_bias=(bias_scale * rng.standard_normal(
                         spec["experts"])).astype(np.float32),
                     shared=mlp(expert), experts=mlp(expert, held))
        else:
            p["mlp"] = mlp(dense)
        return p

    params = {"embed": mat(vocab, dim) * np.float32(np.sqrt(vocab)),
              "norm": norm(), "head": mat(dim, vocab)}
    for i in range(layers):
        params[f"layer{i}"] = layer(i >= spec["dense_layers"])
    params["mtp"] = {"enorm": norm(), "hnorm": norm(),
                     "eh_proj": mat(2 * dim, dim), "layer": layer(True),
                     "norm": norm()}
    return params


def _rms(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


def _rotary(x, spec):
    """x: [T, ..., rope]; dimensions 2i and 2i + 1 turn together."""
    t, dim = x.shape[0], x.shape[-1]
    inv = 1.0 / spec["theta"] ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    angles = np.arange(t, dtype=np.float64)[:, None] * inv[None, :]
    shape = (t,) + (1,) * (x.ndim - 2) + (dim // 2,)
    cos = jnp.asarray(np.cos(angles), jnp.float32).reshape(shape)
    sin = jnp.asarray(np.sin(angles), jnp.float32).reshape(shape)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     -1).reshape(x.shape)


@jax.checkpoint
def _attend(q, k, v, first):
    """One block of queries (positions ``first`` ...) against every key,
    masked: q [Tq, H, 192], k [T, H, 192], v [T, H, 128] -> [Tq, H, 128]."""
    scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(q.shape[-1])
    seen = (first + jnp.arange(q.shape[0]))[:, None] >= jnp.arange(k.shape[0])
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,khe->qhe", probs, v)


def _attention(p, h, spec):
    t = h.shape[0]
    nope, rope, vd = spec["nope"], spec["rope"], spec["v"]
    heads = p["kv_b"].shape[1] // (nope + vd)
    rank = p["kv_a_norm"].shape[0]
    a = _rms(h, p["attn_norm"], spec["eps"])
    c_q = _rms(a @ p["q_a"], p["q_a_norm"], spec["eps"])
    q = (c_q @ p["q_b"]).reshape(t, heads, nope + rope)
    ckv = a @ p["kv_a"]
    kv = (_rms(ckv[:, :rank], p["kv_a_norm"], spec["eps"]) @ p["kv_b"]
          ).reshape(t, heads, nope + vd)
    k_pe = _rotary(ckv[:, rank:], spec)                     # [T, rope]
    q = jnp.concatenate([q[..., :nope], _rotary(q[..., nope:], spec)], -1)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        k_pe[:, None, :], (t, heads, rope))], -1)
    v = kv[..., nope:]
    # Blocks of Q_BLOCK queries (a row that is no multiple of it: one
    # block), one after the other through ONE piece of code.
    block = Q_BLOCK if t % Q_BLOCK == 0 else t
    out = jax.lax.map(lambda b: _attend(b[0], k, v, b[1]),
                      (q.reshape(t // block, block, heads, nope + rope),
                       block * jnp.arange(t // block)))
    return h + out.reshape(t, heads * vd) @ p["o"]


def _gated(p, m):
    return (jax.nn.silu(m @ p["gate"]) * (m @ p["up"])) @ p["down"]


def _experts(p, m, spec):
    scores = jax.nn.sigmoid(m @ p["router"])                # [T, E_pub]
    _, idx = jax.lax.top_k(scores + p["router_bias"], spec["top_k"])
    top = jnp.take_along_axis(scores, idx, axis=-1)
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


def _layer(p, h, spec):
    h = _attention(p, h, spec)
    m = _rms(h, p["mlp_norm"], spec["eps"])
    if "mlp" in p:
        return h + _gated(p["mlp"], m)
    return h + _experts(p, m, spec)


def _hidden(params, tokens, spec):
    """[T] ids -> [T, d], the last layer's output before the final norm."""
    h = params["embed"][tokens]
    for i in range(sum(k.startswith("layer") for k in params)):
        h = jax.checkpoint(lambda p, h_: _layer(p, h_, spec))(
            params[f"layer{i}"], h)
    return h


def _mtp_logits(params, h, nxt, spec):
    """The module's logits [T, V] from h [T, d] and the next ids [T]."""
    p = params["mtp"]
    e = params["embed"][jnp.maximum(nxt, 0)]
    x = jnp.concatenate([_rms(e, p["enorm"], spec["eps"]),
                         _rms(h, p["hnorm"], spec["eps"])], -1) @ p["eh_proj"]
    x = jax.checkpoint(lambda q, x_: _layer(q, x_, spec))(p["layer"], x)
    return _rms(x, p["norm"], spec["eps"]) @ params["head"]


def forward(params, x, spec=PUBLISHED):
    """[B, T] int32 token ids -> [B, T, V] logits."""
    return jnp.stack([_rms(_hidden(params, row, spec), params["norm"],
                           spec["eps"]) @ params["head"] for row in x])


def objective(params, x, y, w, spec=PUBLISHED):
    """The main loss plus ``spec["mtp_weight"]`` times the module's."""
    hs = [_hidden(params, row, spec) for row in x]
    logits = jnp.stack([_rms(h, params["norm"], spec["eps"]) @ params["head"]
                        for h in hs])
    loss = token_cross_entropy(logits, y, w)
    if not spec["mtp_weight"]:
        return loss
    after = jnp.concatenate([y[:, 1:], jnp.full_like(y[:, :1], -1)], 1)
    after = jnp.where(y >= 0, after, -1)
    mtp = jnp.stack([_mtp_logits(params, h, row, spec)
                     for h, row in zip(hs, y)])
    return loss + spec["mtp_weight"] * token_cross_entropy(mtp, after, w)
