"""The mixture-of-experts decoder (``dopt.models.decoder``,
``model="decoder"``) against the plain references of its three layers
(``benchmark/reference_models/laguna_xs2.py``: gated window/full
attention, sigmoid-routed experts beside a shared one;
``keye_vl2.py``: a learned sparse attention with its lightning indexer
and alignment term, softmax-routed experts; ``joyai_llm_flash.py``:
multi-head latent attention, experts chosen by score plus a correction
bias, a multi-token-prediction module in the loss), at toy widths on the
CPU in float32 with seeded random weights.

Tolerances.  Both sides compute in float32 on the CPU (exact products,
no reduced-precision matmul), so what separates them is the ORDER of the
arithmetic: the program takes attention a block of queries against a
slice of the keys, multiplies the combine weight in before the experts'
down projection where the reference scales its result, sums the loss a
block of positions at a time, and forms the rotary angles in float32
where the reference rounds float64 tables.  That is a few float32 ulps
of the largest value after five layers: ``RTOL`` = 2e-5 of the largest
reference value, an order of magnitude above what was read (2e-6 on
logits, 1.3e-6 on gradients) and three below what a dropped term or a
wrong mask gives (1e-2 and more).
"""

import functools
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import adapter, reference
from benchmark.reference_models import joyai_llm_flash as joy
from benchmark.reference_models import keye_vl2 as keye
from benchmark.reference_models import laguna_xs2 as ref
from dopt.config import (DataConfig, DecoderConfig, ExperimentConfig,
                         GossipConfig, ModelConfig, OptimizerConfig)
from dopt.models.decoder import (GatedMoEDecoder, _gated_mlp,
                                 blocked_causal_attention,
                                 indexed_causal_attention, select_top_keys)

ROOT = Path(__file__).resolve().parents[1]
RTOL = 2e-5
PUBLISHED = json.loads(
    (ROOT / "benchmark/configs/laguna-xs2.json").read_text())
VOCAB, DIM, T = 40, 32, 21
# The published constants at toy size: 8-wide heads, 2 key/value heads, a
# band of 6, 16 published experts, 4 a token.
TOY = dict(ref.PUBLISHED, head_dim=8, kv_heads=2, window=6, experts=16,
           top_k=4)
# ... and the sparse-attention model's: rows of 48 positions of which a
# query keeps 8 (so the selection drops keys from position 8 on), an
# indexer of 4 heads of 8.
KEYE = json.loads(
    (ROOT / "benchmark/configs/keye-vl2-30b-a3b.json").read_text())
KEYE_T = 48
KEYE_TOY = dict(keye.PUBLISHED, head_dim=8, kv_heads=2, experts=16, top_k=4,
                index_heads=4, index_dim=8, index_top=8)
# ... and the latent-attention model's: 4 heads of 8 + 8 over values of 8,
# ranks 24 and 16, 16 published experts, 4 a token.
JOYAI = json.loads(
    (ROOT / "benchmark/configs/joyai-llm-flash.json").read_text())
JOYAI_TOY = dict(joy.PUBLISHED, nope=8, rope=8, v=8, experts=16, top_k=4)


def toy_decoder(heads, *, full_every=4, dense_layers=1, held=4, offset=0,
                **kw) -> DecoderConfig:
    """The published configuration with the toy's sizes: ``heads`` query
    heads by layer, layer i full where ``i % full_every == 0`` and dense
    where ``i < dense_layers``, as the reference's ``spec`` has them."""
    n = len(heads)
    body = {**PUBLISHED["model"]["decoder"],
            "hidden_size": DIM, "intermediate_size": 64,
            "num_hidden_layers": n, "num_key_value_heads": TOY["kv_heads"],
            "head_dim": TOY["head_dim"],
            "num_attention_heads_per_layer": heads,
            "layer_types": ["sliding_attention" if i % full_every
                            else "full_attention" for i in range(n)],
            "mlp_layer_types": ["dense" if i < dense_layers else "sparse"
                                for i in range(n)],
            "sliding_window": TOY["window"], "num_experts": TOY["experts"],
            "num_experts_per_tok": TOY["top_k"],
            "moe_intermediate_size": 16,
            "shared_expert_intermediate_size": 16,
            "experts_held": held, "expert_offset": offset, **kw}
    return DecoderConfig(**body)


def toy_model(heads, *, attn_block=8, head_block=16, **kw) -> GatedMoEDecoder:
    """... as a worker whose blocks are shorter than the toy's rows (21
    positions a row, 63 a batch), so that attention takes three blocks
    with a band and the head pads its last one."""
    return GatedMoEDecoder(toy_decoder(heads, **kw), vocab_rows=VOCAB,
                           attn_block=attn_block, head_block=head_block)


def keye_decoder(layers=3, *, held=4, offset=0, topk=8, **kw) -> DecoderConfig:
    """The published sparse-attention configuration with the toy's
    sizes."""
    sa = {**KEYE["sa_config"], "indexer_num_heads": KEYE_TOY["index_heads"],
          "indexer_head_dim": KEYE_TOY["index_dim"], "topk": topk}
    body = {**KEYE["model"]["decoder"],
            "hidden_size": DIM, "intermediate_size": 64,
            "num_hidden_layers": layers, "num_attention_heads": 4,
            "num_key_value_heads": KEYE_TOY["kv_heads"],
            "head_dim": KEYE_TOY["head_dim"],
            "num_experts": KEYE_TOY["experts"],
            "num_local_experts": KEYE_TOY["experts"],
            "num_experts_per_tok": KEYE_TOY["top_k"],
            "moe_intermediate_size": 16, "sa_config": sa,
            "rope_scaling": {"mrope_section": [1, 1, 2],
                             "rope_type": "default", "type": "default"},
            "experts_held": held, "expert_offset": offset, **kw}
    return DecoderConfig(**body)


def keye_model(layers=3, *, attn_block=4, head_block=16, **kw):
    """... as a worker whose blocks of 4 queries go 8 to a run (32
    positions): a row of 48 takes two runs, with 32 and 48 keys."""
    return GatedMoEDecoder(keye_decoder(layers, **kw), vocab_rows=VOCAB,
                           attn_block=attn_block, head_block=head_block)


def joyai_decoder(layers=3, *, held=4, offset=0, **kw) -> DecoderConfig:
    """The published latent-attention configuration with the toy's
    sizes."""
    body = {**JOYAI["model"]["decoder"],
            "hidden_size": DIM, "intermediate_size": 64,
            "moe_intermediate_size": 16, "num_hidden_layers": layers,
            "num_attention_heads": 4, "num_key_value_heads": 4,
            "head_dim": JOYAI_TOY["rope"],
            "qk_rope_head_dim": JOYAI_TOY["rope"],
            "qk_nope_head_dim": JOYAI_TOY["nope"],
            "qk_head_dim": JOYAI_TOY["nope"] + JOYAI_TOY["rope"],
            "v_head_dim": JOYAI_TOY["v"], "q_lora_rank": 24,
            "kv_lora_rank": 16, "n_routed_experts": JOYAI_TOY["experts"],
            "num_experts_per_tok": JOYAI_TOY["top_k"],
            "experts_held": held, "expert_offset": offset, **kw}
    return DecoderConfig(**body)


def joyai_model(layers=3, *, attn_block=8, head_block=16, **kw):
    """... as a worker whose blocks are shorter than the toy's rows."""
    return GatedMoEDecoder(joyai_decoder(layers, **kw), vocab_rows=VOCAB,
                           attn_block=attn_block, head_block=head_block)


def joyai_params(seed=0, layers=3, held=4, bias_scale=0.0):
    return jax.tree.map(jnp.asarray, joy.init(
        seed, JOYAI_TOY, vocab=VOCAB, dim=DIM, heads=4, layers=layers,
        dense=64, expert=16, held=held, q_rank=24, kv_rank=16,
        bias_scale=bias_scale))


def keye_params(seed=0, layers=3, held=4):
    return jax.tree.map(jnp.asarray, keye.init(
        seed, KEYE_TOY, vocab=VOCAB, dim=DIM, heads=4, layers=layers,
        expert=16, held=held))


def batch(seed=1, rows=3, t=T):
    rng = np.random.default_rng(seed)
    x = rng.integers(0, VOCAB, (rows, t)).astype(np.int32)
    y = np.concatenate([x[:, 1:], np.full((rows, 1), -1, np.int32)], 1)
    w = np.ones(rows, np.float32)
    w[-1] = 0.0                    # a padding row: no position of it counts
    return x, y, w


def close(got, want):
    want = np.asarray(want)
    return np.abs(np.asarray(got) - want).max() <= RTOL * np.abs(want).max()


# layer kinds covered: full+dense, sliding+sparse, full+sparse (the
# published pattern, 4 and 6 query heads standing for 48 and 64); then
# sliding+dense with MORE heads in the full layer, a lone full+dense
# layer, and full+sparse first.
PATTERNS = {
    "published-pattern": ([4, 6, 6, 6, 4], {}),
    "sliding-dense": ([6, 4], {"dense_layers": 2}),
    "one-layer": ([4], {}),
    "sparse-first": ([4, 6], {"dense_layers": 0}),
}
# the sparse-attention layer: three layers whose every query keeps 8 keys
# (both runs of blocks select); two that keep 40 (the first run, of 32
# keys, keeps all it sees and the second selects) in blocks of 20
# queries, so that the row ends in a shorter block of its own.
KEYE_PATTERNS = {
    "keye-top8": (3, {}),
    "keye-top40-ragged": (2, {"topk": 40, "attn_block": 20}),
}
# the latent-attention layer: a dense layer, two with experts and the
# multi-token-prediction module; the same with a correction bias that
# moves the choice; a lone dense layer (the module's block is then the
# only layer with experts).
JOYAI_PATTERNS = {
    "joyai-mtp": (3, 0.0),
    "joyai-biased-router": (3, 0.5),
    "joyai-one-dense-layer": (1, 0.0),
}
ALL_PATTERNS = sorted({**PATTERNS, **KEYE_PATTERNS, **JOYAI_PATTERNS})


def case(name):
    """(model, seeded parameters, (x, y, w), the reference's forward and
    objective) for one pattern of any model type."""
    if name in JOYAI_PATTERNS:
        layers, bias = JOYAI_PATTERNS[name]
        return (joyai_model(layers), joyai_params(0, layers, bias_scale=bias),
                batch(), functools.partial(joy.forward, spec=JOYAI_TOY),
                functools.partial(joy.objective, spec=JOYAI_TOY))
    if name in KEYE_PATTERNS:
        layers, kw = KEYE_PATTERNS[name]
        spec = {**KEYE_TOY, "index_top": kw.get("topk", 8)}
        return (keye_model(layers, **kw), keye_params(0, layers),
                batch(t=KEYE_T),
                functools.partial(keye.forward, spec=spec),
                functools.partial(keye.objective, spec=spec))
    heads, kinds = PATTERNS[name]
    spec = {**TOY, **kinds}
    params = jax.tree.map(jnp.asarray, ref.init(
        0, spec, vocab=VOCAB, dim=DIM, heads=heads, dense=64, expert=16,
        held=4))
    return (toy_model(heads, **kinds), params, batch(),
            functools.partial(ref.forward, spec=spec),
            functools.partial(ref.objective, spec=spec))


@pytest.fixture(scope="module", params=ALL_PATTERNS)
def both(request):
    """(program's logits, loss, gradients, aux), (reference's) for one
    pattern of layers, from the same seeded parameters and batch."""
    model, params, (x, y, w), forward, objective = case(request.param)
    (loss, aux), grads = jax.value_and_grad(
        lambda p: model.loss(p, x, y, w), has_aux=True)(params)
    want_loss, want_grads = jax.value_and_grad(
        lambda p: objective(p, x, y, w))(params)
    return ((model.apply({"params": params}, x), loss, grads, aux),
            (forward(params, x), want_loss, want_grads))


def test_logits_equal_the_reference(both):
    got, want = both
    assert close(got[0], want[0])


def test_loss_equals_the_reference(both):
    got, want = both
    assert abs(float(got[1]) - float(want[1])) <= RTOL * float(want[1])
    assert 0.0 <= float(got[3]["acc"]) <= 1.0


def test_gradients_equal_the_reference(both):
    """Leaf by leaf, each held to its own largest value: a router's, a
    gate's or an indexer's gradient is orders of magnitude under the
    head's."""
    got, want = both
    bad = {jax.tree_util.keystr(k) for (k, g), w in zip(
        jax.tree_util.tree_leaves_with_path(got[2]),
        jax.tree.leaves(want[2])) if not close(g, w)}
    assert not bad


def test_init_builds_the_reference_tree_and_the_published_count():
    heads = [4, 6, 6, 6, 4]
    model = toy_model(heads)
    mine = model.init(jax.random.key(0))["params"]
    theirs = ref.init(0, TOY, vocab=VOCAB, dim=DIM, heads=heads, dense=64,
                      expert=16, held=4)
    assert (jax.tree.map(lambda a: a.shape, mine)
            == jax.tree.map(lambda a: a.shape, theirs))
    # At the published sizes, from shapes alone (nothing is allocated).
    body = PUBLISHED["model"]
    full = GatedMoEDecoder(DecoderConfig(**body["decoder"]),
                           vocab_rows=body["num_classes"])
    shapes = jax.eval_shape(full.init, jax.random.key(0))
    assert sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes)) \
        == PUBLISHED["parameters"] == 389_634_048


def test_init_builds_the_latent_attention_tree_and_the_published_count():
    """The toy's tree is the reference's (a multi-token-prediction module
    beside the layers, a correction bias of zeros in each layer with
    experts), and the configuration file's worker holds its
    491,697,408 parameters, from shapes alone."""
    mine = joyai_model().init(jax.random.key(0))["params"]
    assert (jax.tree.map(lambda a: a.shape, mine)
            == jax.tree.map(lambda a: a.shape, joyai_params()))
    assert "mlp" in mine["layer0"] and "router_bias" in mine["layer1"]
    assert float(jnp.abs(mine["mtp"]["layer"]["router_bias"]).max()) == 0.0
    body = ModelConfig(**JOYAI["model"])
    full = GatedMoEDecoder(body.decoder, vocab_rows=body.num_classes)
    shapes = jax.eval_shape(full.init, jax.random.key(0))
    assert sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes)) \
        == JOYAI["parameters"] == 491_697_408


def test_init_builds_the_sparse_attention_tree_and_its_count():
    mine = keye_model().init(jax.random.key(0))["params"]
    assert (jax.tree.map(lambda a: a.shape, mine)
            == jax.tree.map(lambda a: a.shape, keye_params()))
    layer = mine["layer0"]
    assert "gate" not in layer and "shared" not in layer
    assert float(jnp.abs(layer["indexer"]["k_bias"]).max()) == 0.0
    body = ModelConfig(**KEYE["model"])
    full = GatedMoEDecoder(body.decoder, vocab_rows=body.num_classes)
    shapes = jax.eval_shape(full.init, jax.random.key(0))
    assert sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes)) \
        == KEYE["parameters"] == 314_396_160


# ------------------------------------------------------- the shares add up

@pytest.mark.parametrize("held", [1, 4, 8, 16])
@pytest.mark.parametrize("model_type", ["laguna", "KeyeVL2", "joyai_llm_flash"])
def test_the_shares_add_up_to_the_uncut_layer(model_type, held):
    """For every share of the 16 published experts, the layer's routed
    part, plus the shared expert (where the layer has one) counted once,
    is what the uncut reference layer gives: nothing stands in for the
    absent chips, and nothing of theirs is lost or doubled."""
    m = jnp.asarray(np.random.default_rng(3).standard_normal(
        (T, DIM)).astype(np.float32))
    if model_type == "laguna":
        spec = {**TOY, "dense_layers": 0}
        layer = jax.tree.map(jnp.asarray, ref.init(
            2, spec, vocab=VOCAB, dim=DIM, heads=[4], dense=64, expert=16,
            held=TOY["experts"])["layer0"])
        want = ref._experts(layer, m, spec)
        shared = _gated_mlp(layer["shared"], m, jnp.float32)
    elif model_type == "KeyeVL2":
        layer = keye_params(2, 1, KEYE_TOY["experts"])["layer0"]
        want = keye._experts(layer, m, KEYE_TOY)
        shared = 0.0
    else:      # with a correction bias that moves the choice
        layer = joyai_params(2, 2, JOYAI_TOY["experts"],
                             bias_scale=0.5)["layer1"]
        want = joy._experts(layer, m, JOYAI_TOY)
        shared = _gated_mlp(layer["shared"], m, jnp.float32)
    total, slots = shared, 0.0
    for offset in range(0, TOY["experts"], held):
        model = {"laguna": lambda: toy_model([4], dense_layers=0, held=held,
                                             offset=offset),
                 "KeyeVL2": lambda: keye_model(1, held=held, offset=offset),
                 "joyai_llm_flash": lambda: joyai_model(
                     2, held=held, offset=offset)}[model_type]()
        share = {**layer, "experts": jax.tree.map(
            lambda a: a[offset:offset + held], layer["experts"])}
        out, counts = model._experts(share, m)
        total = total + (out - shared)
        slots += float(counts["moe_held_slot_share"])
    assert close(total, want)
    # every routed slot reached exactly one share
    assert abs(slots - 1.0) < 1e-6


@pytest.mark.parametrize("model_type", ["laguna", "KeyeVL2", "joyai_llm_flash"])
def test_routing_counts_are_counts(model_type):
    model = {"laguna": lambda: toy_model([4, 6]),
             "KeyeVL2": lambda: keye_model(2),
             "joyai_llm_flash": lambda: joyai_model(2)}[model_type]()
    params = model.init(jax.random.key(1))["params"]
    x, y, w = batch(t=T if model_type == "laguna" else KEYE_T)
    _, aux = model.loss(params, x, y, w)
    assert set(aux) == {"acc", *model.counters}
    # 4 of 16 held: a quarter of the slots in expectation, never over
    # top_k * held / (top_k * 1) and the fullest expert at least the mean
    assert 0.0 < float(aux["moe_held_slot_share"]) < 1.0
    assert float(aux["moe_load_max_over_mean"]) >= 1.0
    if model_type == "KeyeVL2":
        # exactly min(t + 1, 8) of the t + 1 visible keys a query: a count
        kept = sum(min(t + 1, 8) for t in range(KEYE_T))
        assert float(aux["index_keys_kept_share"]) == pytest.approx(
            kept / (KEYE_T * (KEYE_T + 1) / 2), rel=1e-6)
        assert float(aux["index_align_loss"]) > 0.0     # a KL divergence
    if model_type == "joyai_llm_flash":
        assert float(aux["mtp_loss"]) > 0.0
        assert 0.0 <= float(aux["mtp_acc"]) <= 1.0


# ------------------------------------------- the learned sparse attention

def test_each_loss_term_trains_its_own_leaves():
    """The indexer's leaves get the alignment term's gradient alone and
    every other leaf the cross-entropy's alone: exact zeros, not small
    numbers (the indexer's input is detached, the head-mean of the
    probabilities is a constant of the alignment term and the selection
    is not differentiated)."""
    model, params = keye_model(), keye_params()
    x, y, w = batch(t=KEYE_T)
    layers = model.cfg.num_hidden_layers

    def terms(p):
        loss, aux = model.loss(p, x, y, w)
        align = layers * aux["index_align_loss"]
        return loss - align, align

    by_ce = jax.grad(lambda p: terms(p)[0])(params)
    by_align = jax.grad(lambda p: terms(p)[1])(params)
    for path, g in jax.tree_util.tree_leaves_with_path(by_ce):
        mine = "indexer" in jax.tree_util.keystr(path)
        assert (float(jnp.abs(g).max()) == 0.0) == mine, path
    for path, g in jax.tree_util.tree_leaves_with_path(by_align):
        mine = "indexer" in jax.tree_util.keystr(path)
        assert (float(jnp.abs(g).max()) > 0.0) == mine, path


@pytest.mark.parametrize("tq, tk, top, scores", [
    (8, 48, 8, "normal"),         # every row drops keys
    (48, 48, 8, "normal"),        # the first rows see fewer than they keep
    (40, 300, 130, "normal"),     # three chunks of the running count
    (48, 48, 8, "levels"),        # many ties at the k-th place
    (48, 48, 8, "zeros"),         # all tied, -0.0 among them: the first k
    (16, 200, 1, "levels"),       # one key a query
])
def test_the_selection_keeps_exactly_k_keys_ties_to_the_lower_position(
        tq, tk, top, scores):
    """``select_top_keys`` (bisection on the score's bits, a running
    count for the ties) against the reference's stable sort: the same
    mask, with exactly min(t + 1, k) keys a query."""
    rng = np.random.default_rng(tk + top)
    index = {"normal": rng.standard_normal((tq, tk)),
             "levels": rng.integers(-2, 3, (tq, tk)) * 0.5,
             "zeros": np.where(rng.random((tq, tk)) < 0.5, 0.0, -0.0)
             }[scores].astype(np.float32)
    at = tk - tq + np.arange(tq)                  # the row's last queries
    seen = jnp.asarray(np.arange(tk)[None, :] <= at[:, None])
    count = jnp.minimum(jnp.asarray(at) + 1, top)
    got = select_top_keys(jnp.asarray(index), seen, count)
    np.testing.assert_array_equal(got.sum(-1), count)
    assert not bool((got & ~seen).any())
    np.testing.assert_array_equal(
        got, keye.select(jnp.asarray(index), seen, top))
    if scores == "zeros":         # the diagonal's rule: the lowest positions
        np.testing.assert_array_equal(
            got, np.arange(tk)[None, :] < np.asarray(count)[:, None])


@pytest.mark.parametrize("t, block", [(48, 4), (21, 8), (37, 16)])
def test_keeping_every_key_is_dense_causal_attention(t, block):
    """With ``topk`` >= T the selection drops nothing: the indexed body
    is causal attention whatever the indexer says, every visible key is
    counted and T need not be a multiple of the block."""
    rng = np.random.default_rng(t)

    def draw(*shape):
        return jnp.asarray(rng.standard_normal(shape).astype(np.float32))

    q, k, v = draw(2, 3, t, 8), draw(2, t, 8), draw(2, t, 8)
    got, align, kept = indexed_causal_attention(
        q, k, v, draw(4, t, 8), draw(t, 8), draw(t, 4), topk=t, block=block)
    assert close(got, blocked_causal_attention(q, k, v, window=None,
                                               block=block))
    assert float(kept) == pytest.approx(1.0, rel=1e-6)
    assert float(align) > 0.0


# ----------------------------------------------------- banded attention

@pytest.mark.parametrize("t, window, block", [
    (21, 6, 8), (37, 6, 16), (37, None, 16), (21, 200, 8), (300, 130, 64)])
def test_blocked_attention_equals_masked_full_attention(t, window, block):
    """T is not a multiple of the block in any case; a band wider than
    the row is full attention; (300, 130, 64) has a band whose aligned
    start is past 0."""
    rng = np.random.default_rng(t)
    q = jnp.asarray(rng.standard_normal((2, 3, t, 8)).astype(np.float32))
    k, v = (jnp.asarray(rng.standard_normal((2, t, 8)).astype(np.float32))
            for _ in range(2))
    got = blocked_causal_attention(q, k, v, window=window, block=block)
    scores = jnp.einsum("grqd,gkd->grqk", q, k) / np.sqrt(8)
    back = np.arange(t)[:, None] - np.arange(t)[None, :]
    seen = (back >= 0) & (back < (window or t))
    want = jnp.einsum("grqk,gkd->grqd", jax.nn.softmax(
        jnp.where(seen, scores, -jnp.inf), axis=-1), v)
    assert close(got, want)


# -------------------------------------------------- through the engine

def gossip_config(model_type="laguna", **model_kw) -> ExperimentConfig:
    """Two workers of the toy of any model type under the traffic of
    its cell (``model="laguna"`` is the first one's name for
    ``"decoder"``, and stays valid)."""
    laguna = model_type == "laguna"
    mix, decoder, t = {
        "laguna": ("localsgd2-t4096", lambda: toy_decoder([4, 6, 6],
                                                          **model_kw), T),
        "KeyeVL2": ("localsgd2-t8192", lambda: keye_decoder(2, **model_kw),
                    KEYE_T),
        "joyai_llm_flash": ("localsgd2-t4096-h4",
                            lambda: joyai_decoder(2, **model_kw), T),
    }[model_type]
    traffic = json.loads(
        (ROOT / "benchmark/traffic" / f"{mix}.json").read_text())
    return ExperimentConfig(
        name="decoder-toy", seed=3, mesh_devices=1,
        data=DataConfig(dataset="synthetic_tokens", num_users=2, iid=True,
                        synthetic_train_size=8, synthetic_test_size=2),
        model=ModelConfig(
            model="laguna" if laguna else "decoder", faithful=False,
            num_classes=VOCAB, input_shape=(t,), decoder=decoder()),
        optim=OptimizerConfig(lr=0.05, momentum=0.9),
        gossip=GossipConfig(**{**traffic["gossip"], "local_bs": 2}))


@pytest.mark.parametrize("model_type", ["laguna", "KeyeVL2", "joyai_llm_flash"])
def test_two_gossip_rounds_equal_the_reference_loop(model_type):
    """``GossipTrainer`` on token rows against ``benchmark.reference``'s
    gossip loop: same initial parameters, batches and mixing matrices,
    two rounds of two steps a worker."""
    from dopt.engine import GossipTrainer

    cfg = gossip_config(model_type)
    traffic = {"engine": "gossip", "eval": "none"}
    trainer = adapter.build_trainer(cfg, traffic)
    assert isinstance(trainer, GossipTrainer)
    init = adapter.initial_params(trainer, traffic)
    rounds = adapter.reference_rounds(trainer, cfg, traffic, 2)
    trainer.run(rounds=2)
    got = adapter.final_params(trainer, traffic)
    objective = {
        "laguna": functools.partial(ref.objective, spec=TOY),
        "KeyeVL2": functools.partial(keye.objective, spec=KEYE_TOY),
        "joyai_llm_flash": functools.partial(joy.objective, spec=JOYAI_TOY),
    }[model_type]
    want = reference.run_gossip(
        objective, init, rounds, lr=cfg.optim.lr,
        momentum=cfg.optim.momentum)
    moved = reference.max_abs_error(want, [init] * 2)
    assert moved > 1e-3
    assert reference.max_abs_error(got, want) <= 1e-4 * moved
    rows = trainer.history.rows
    assert [r["round"] for r in rows] == [0, 1]
    for r in rows:
        assert np.isfinite(r["avg_train_loss"])
        assert 0.0 < r["moe_held_slot_share"] < 1.0
        assert r["moe_load_max_over_mean"] >= 1.0
        if model_type == "KeyeVL2":
            assert r["index_align_loss"] > 0.0
            assert 0.0 < r["index_keys_kept_share"] < 1.0
        if model_type == "joyai_llm_flash":
            assert r["mtp_loss"] > 0.0 and 0.0 <= r["mtp_acc"] <= 1.0


def test_the_cell_averages_the_two_workers():
    from dopt.engine import GossipTrainer

    trainer = GossipTrainer(gossip_config(), eval_every=10**9)
    for t in range(3):
        np.testing.assert_array_equal(trainer.mixing.for_round(t),
                                      [[0.5, 0.5], [0.5, 0.5]])


def test_blocked_rounds_equal_per_round_rows():
    """The fused multi-round path carries the routing counts too."""
    from dopt.engine import GossipTrainer

    a = GossipTrainer(gossip_config(), eval_every=10**9)
    b = GossipTrainer(gossip_config(), eval_every=10**9)
    a.run(rounds=2)
    b.run(rounds=2, block=2)
    for ra, rb in zip(a.history.rows, b.history.rows):
        assert ra.keys() == rb.keys()
        for k in ra:
            assert ra[k] == pytest.approx(rb[k], rel=1e-5), k


def test_refusals():
    from dopt.data import partition
    from dopt.engine import GossipTrainer
    from dopt.models import build_model

    with pytest.raises(ValueError, match="local_holdout"):
        cfg = gossip_config()
        GossipTrainer(cfg.replace(data=DataConfig(
            **{**cfg.data.__dict__, "local_holdout": 0.1})))
    with pytest.raises(ValueError, match="IID only"):
        partition(np.zeros((8, T), np.int32), 2, iid=False)
    with pytest.raises(ValueError, match="needs ModelConfig.decoder"):
        build_model("laguna")
    with pytest.raises(TypeError):       # a key the dataclass lacks
        ModelConfig(model="laguna", decoder={"no_such_key": 1})
    with pytest.raises(ValueError, match="not among"):
        toy_decoder([4], held=4, offset=14)
    with pytest.raises(ValueError, match="needs ModelConfig.decoder"):
        build_model("decoder")
    with pytest.raises(ValueError, match="model='decoder' only"):
        build_model("mlp", decoder=keye_decoder())


@pytest.mark.parametrize("change, message", [
    ({"model_type": "qwen3_moe"}, "one of laguna"),
    ({"gating": False}, "a key of model_type 'laguna'"),
    ({"shared_expert_intermediate_size": 16}, "a key of model_type 'laguna'"),
    ({"sa_config": None}, "sa_config is required"),
    ({"rope_theta": None}, "rope_theta is required"),
    ({"num_attention_heads": None}, "num_attention_heads is required"),
    ({"sa_config": {"topk": 8}}, "sa_config has the keys"),
    ({"sa_config": {**KEYE["sa_config"], "indexer_num_kv_heads": 2}},
     "ONE key head"),
    ({"sa_config": {**KEYE["sa_config"], "topk": 0}}, "ONE key head"),
    ({"norm_topk_prob": False}, "renormalises"),
    ({"decoder_sparse_step": 0}, "renormalises"),
    ({"sliding_window": 512}, "renormalises"),
    ({"use_sliding_window": True}, "renormalises"),
    ({"hidden_act": "gelu"}, "renormalises"),
    ({"num_local_experts": 8}, "renormalises"),
    ({"rope_scaling": {"rope_type": "yarn"}}, "plain rotary"),
    ({"rope_scaling": {"mrope_section": [16, 24, 24]}}, "half a head"),
    ({"attention_bias": True}, "no biases"),
    ({"num_attention_heads": 3}, "multiple of"),
])
def test_the_sparse_attention_config_refuses(change, message):
    """A key of the new ``config.json`` that the layer cannot honour, a
    key of the other model type, and a missing one are refused."""
    with pytest.raises(ValueError, match=message):
        keye_decoder(**change)


def test_a_laguna_config_refuses_the_other_type_s_keys():
    with pytest.raises(ValueError, match="a key of model_type 'KeyeVL2'"):
        toy_decoder([4], sa_config=KEYE["sa_config"])
    with pytest.raises(ValueError, match="gating true"):
        toy_decoder([4], gating=False)
    with pytest.raises(ValueError, match="layer_types is required"):
        toy_decoder([4], layer_types=None)
    with pytest.raises(TypeError):       # a key no config.json here has
        keye_decoder(index_n_heads=4)


def test_which_mlp_a_sparse_attention_layer_has():
    """``decoder_sparse_step`` and ``mlp_only_layers`` as Qwen3-MoE
    reads them: the published 1 and [] make every layer sparse."""
    assert all(keye_decoder(4).sparse_mlp(i) for i in range(4))
    cfg = keye_decoder(4, decoder_sparse_step=2, mlp_only_layers=[3])
    assert [cfg.sparse_mlp(i) for i in range(4)] == [False, True, False,
                                                     False]
    tree = GatedMoEDecoder(cfg, vocab_rows=VOCAB).init(
        jax.random.key(0))["params"]
    assert "mlp" in tree["layer0"] and "router" in tree["layer1"]


@pytest.mark.parametrize("model_type", ["laguna", "KeyeVL2", "joyai_llm_flash"])
def test_compiled_round_carries_the_decoder_scopes(model_type):
    """``dopt_attn``, ``dopt_moe`` with ``dopt_route`` inside it and
    ``dopt_head`` mark the decoder's forward and backward inside the
    local phase; the update and the mix keep their own scopes.  A layer
    with an indexer also has ``dopt_index`` with ``dopt_select`` inside
    it and ``dopt_attend``, all inside ``dopt_attn``; the selection has
    no backward pass."""
    import re

    from jax._src.config import enable_compilation_cache

    from dopt.engine import GossipTrainer

    _, lowered = GossipTrainer(gossip_config(model_type),
                               eval_every=10**9).lower_round(1)
    with enable_compilation_cache(False):   # the key ignores metadata
        text = lowered.compile().as_text()
    stacks = {s for s in re.findall(r'op_name="([^"]*)"', text)
              if s.startswith("jit(")}
    indexed = ("dopt_index", "dopt_attend")
    for scope in ("dopt_attn", "dopt_moe", "dopt_route", "dopt_head",
                  *(indexed if model_type == "KeyeVL2" else ())):
        mine = {s for s in stacks if scope in s}
        assert mine, scope
        assert all("dopt_local" in s for s in mine if "dopt_eval" not in s)
        assert any("transpose(jvp(" in s for s in mine), scope
    for scope in (*indexed, "dopt_select"):
        mine = {s for s in stacks if scope in s}
        assert bool(mine) == (model_type == "KeyeVL2"), scope
        assert all("dopt_attn" in s for s in mine), scope
    assert all("dopt_index" in s for s in stacks if "dopt_select" in s)
    # ... it runs again in a block's recompute and never as a transpose
    assert all("rematted_computation" in s for s in stacks
               if "dopt_select" in s and "transpose(jvp(" in s)
    assert not [s for s in stacks if "dopt_index" in s and "dopt_attend" in s]
    assert all("dopt_moe" in s for s in stacks if "dopt_route" in s)
    assert not [s for s in stacks if "dopt_attn" in s and "dopt_moe" in s]
    for scope in ("dopt_update", "dopt_mix", "dopt_batch"):
        assert any(scope in s for s in stacks), scope


def test_the_laguna_round_is_the_parent_s_program():
    """The toy ``laguna`` round lowers to a pinned text (jax 0.9.0 on the
    CPU), so that a change which is not meant to move what the
    ``laguna-xs2`` cell compiles is seen not to.  PR 32 put a second
    layer into the decoder and left the hash of commit 975eb78 standing
    (``c208f2b4...``); PR 34 moved it on purpose: the held experts' dense
    dispatch became the sorted slots' grouped matmul, and the router
    takes its values at kept indices.  Then it moved on purpose again:
    one function per layer kind (``GatedMoEDecoder._body``), called by
    each layer of the kind, where every layer was written out (on
    XLA:CPU the compiled toy holds the same instructions)."""
    import hashlib

    from dopt.engine import GossipTrainer

    _, lowered = GossipTrainer(gossip_config(),
                               eval_every=10**9).lower_round(1)
    assert hashlib.sha256(lowered.as_text().encode()).hexdigest() == (
        "5ff897a5812409fe12eaab55cdea0abd5273dd24b69cbdf4c2d27a118c72855e")


ANATOMY = ("dopt_qkv", "dopt_rope", "dopt_oproj")
# name: (model type, toy keys, has a dense layer, has a shared expert)
LAYER_KINDS = {
    "laguna": ("laguna", {}, True, True),
    "laguna-no-dense-layer": ("laguna", {"dense_layers": 0}, False, True),
    "keye": ("KeyeVL2", {}, False, False),
    "keye-a-dense-layer": ("KeyeVL2", {"mlp_only_layers": [1]}, True, False),
    "joyai": ("joyai_llm_flash", {}, True, True),
}


def _stacks(text):
    import re

    return {s for s in re.findall(r'op_name="([^"]*)"', text)
            if s.startswith("jit(")}


def _check_scope(stacks, scope, parent):
    """``scope`` marks forward and backward ops, all under ``parent`` (but
    in the round's own evaluation, which is not in the local phase)."""
    mine = {s for s in stacks if scope in s}
    assert mine, scope
    assert all(parent in s for s in mine if "dopt_eval" not in s), scope
    assert any("transpose(jvp(" not in s for s in mine), scope
    assert any("transpose(jvp(" in s for s in mine), scope


@pytest.mark.parametrize("kind", sorted(LAYER_KINDS))
def test_compiled_round_names_every_part_of_the_layer(kind):
    """``dopt_qkv``, ``dopt_rope`` and ``dopt_oproj`` inside ``dopt_attn``,
    ``dopt_shared`` inside ``dopt_moe`` where the layer has a shared
    expert, ``dopt_mlp`` inside ``dopt_local`` (and outside the attention
    and the experts) where a layer is dense, each forward and backward;
    the indexer keeps its own scope; ``dopt_splash`` only on the kernel's
    path, which no toy round takes."""
    from jax._src.config import enable_compilation_cache

    from dopt.engine import GossipTrainer

    model_type, kw, dense, shared = LAYER_KINDS[kind]
    _, lowered = GossipTrainer(gossip_config(model_type, **kw),
                               eval_every=10**9).lower_round(1)
    with enable_compilation_cache(False):   # the key ignores metadata
        stacks = _stacks(lowered.compile().as_text())
    for scope in ANATOMY:
        _check_scope(stacks, scope, "dopt_attn")
        assert not [s for s in stacks if scope in s and (
            "dopt_index" in s or "dopt_attend" in s)], scope
    for scope, present, parent in [("dopt_mlp", dense, "dopt_local"),
                                   ("dopt_shared", shared, "dopt_moe")]:
        if present:
            _check_scope(stacks, scope, parent)
        else:
            assert not [s for s in stacks if scope in s], scope
    assert not [s for s in stacks if "dopt_mlp" in s
                and ("dopt_attn" in s or "dopt_moe" in s)]
    assert not [s for s in stacks if "dopt_shared" in s and "dopt_route" in s]
    assert not [s for s in stacks if "dopt_splash" in s]


# The layers of each kind, in order, of each LAYER_KINDS toy at its cell's
# depth: five laguna layers (full / sliding x 3 / full, the first dense
# where the toy has a dense layer), four keye layers.
KIND_LAYERS = {
    "laguna": [[0], [1, 2, 3], [4]],
    "laguna-no-dense-layer": [[0, 4], [1, 2, 3]],
    "keye": [[0, 1, 2, 3]],
    "keye-a-dense-layer": [[0, 2, 3], [1]],
    "joyai": [[0], [1, 2, 3, 4]],
}


def kinds_toy(kind):
    """(the LAYER_KINDS toy at KIND_LAYERS' depth, a batch of its rows)."""
    model_type, kw, *_ = LAYER_KINDS[kind]
    if model_type == "laguna":
        return toy_model([4, 6, 6, 6, 4], **kw), batch()
    if model_type == "joyai_llm_flash":
        return joyai_model(5, **kw), batch()
    return keye_model(4, **kw), batch(t=KEYE_T)


def _layer_calls(text):
    """{function: calls} of the layer bodies in a lowered module's text."""
    import re

    return {f: text.count(f"call @{f}(")
            for f in re.findall(r"func\.func private @(layer(?:_\d+)?)\(",
                                text)}


@pytest.mark.parametrize("kind", sorted(LAYER_KINDS))
def test_each_layer_kind_is_traced_once_a_branch(kind):
    """One ``jax.jit(jax.checkpoint(...))`` a layer kind: the gradient
    program traces each kind's body once, however many layers it has,
    and lowers it to one function a branch, the forward with its kept
    residuals and the backward, that every layer of the kind calls; the
    round adds its evaluation's forward, at the training rows' shapes in
    these toys, so no second trace.  A multi-token-prediction module's
    block is one more call of its kind's body, not a trace."""
    from dopt.engine import GossipTrainer

    model, (x, y, w) = kinds_toy(kind)
    kinds = model.layer_kinds()
    assert list(kinds.values()) == KIND_LAYERS[kind]
    params = model.init(jax.random.key(0))["params"]
    text = jax.jit(jax.grad(lambda p: model.loss(p, x, y, w)[0])).lower(
        params).as_text()
    assert dict(model.kind_traces) == dict.fromkeys(kinds, 1)
    # each kind's two functions are called once by each of its layers
    # (and by the module's block)
    mtp = model.cfg.mtp and model._kind(model.mtp_layer)
    calls = [len(layers) + (k == mtp) for k, layers in kinds.items()]
    assert sorted(_layer_calls(text).values()) == sorted(calls * 2)

    model_type, kw, *_ = LAYER_KINDS[kind]
    trainer = GossipTrainer(gossip_config(model_type, **kw),
                            eval_every=10**9)
    round_text = trainer.lower_round(1)[1].as_text()
    kinds = trainer.model.layer_kinds()
    assert dict(trainer.model.kind_traces) == dict.fromkeys(kinds, 1)
    calls = _layer_calls(round_text)
    assert len(calls) == 3 * len(kinds)
    assert sum(calls.values()) == 3 * (sum(map(len, kinds.values()))
                                       + trainer.model.cfg.mtp)


def _per_layer_hidden(self, params, tokens):
    """``GatedMoEDecoder._hidden`` as it was before one body a kind: a new
    ``jax.checkpoint`` around a new ``lambda`` for every layer."""
    from dopt.models import decoder

    h = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
    counts = []
    for i in range(self.cfg.num_hidden_layers):
        h, c = jax.checkpoint(
            lambda p, h_, i=i: self._layer(p, h_, i),
            policy=jax.checkpoint_policies.save_only_these_names(
                *decoder.LAYER_KEEPS))(params[f"layer{i}"], h)
        counts.append(c)
    return h, {k: (jnp.mean(jnp.stack([c[k] for c in counts if k in c]))
                   if any(k in c for c in counts) else jnp.zeros(()))
               for k in self.counters}


@pytest.mark.parametrize("kind", sorted(LAYER_KINDS))
def test_one_body_a_kind_equals_a_checkpoint_a_layer(monkeypatch, kind):
    """The loss, every count and every gradient leaf equal those of the
    per-layer ``jax.checkpoint`` form BIT FOR BIT on XLA:CPU (the
    compiled programs hold the same instructions: XLA inlines the
    calls)."""
    model, (x, y, w) = kinds_toy(kind)
    params = model.init(jax.random.key(5))["params"]

    def run():
        return jax.jit(jax.value_and_grad(
            lambda p: model.loss(p, x, y, w), has_aux=True))(params)

    (loss, aux), grads = run()
    traced = dict(model.kind_traces)
    monkeypatch.setattr(GatedMoEDecoder, "_hidden", _per_layer_hidden)
    (want_loss, want_aux), want_grads = run()
    assert dict(model.kind_traces) == traced    # no body in the old form
    assert np.asarray(loss).tobytes() == np.asarray(want_loss).tobytes()
    for a, b in zip(jax.tree.leaves((aux, grads)),
                    jax.tree.leaves((want_aux, want_grads))):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


def test_the_cells_layers_fall_into_their_kinds():
    """Laguna's five layers are three kinds (full attention with 48 heads
    and a dense MLP; sliding, 64 heads, experts; full, 48, experts),
    keye's four one, joyai's five two (dense, experts); from the
    configuration alone."""
    from dopt.presets import get_preset

    for preset, config, want in (
            ("laguna-localsgd2", PUBLISHED, [[0], [1, 2, 3], [4]]),
            ("keye-localsgd2", KEYE, [[0, 1, 2, 3]]),
            ("joyai-localsgd2", JOYAI, [[0], [1, 2, 3, 4]])):
        for model in (get_preset(preset).model,
                      ModelConfig(**config["model"])):
            worker = GatedMoEDecoder(model.decoder, vocab_rows=VOCAB)
            assert list(worker.layer_kinds().values()) == want


def test_the_kernel_s_operands_carry_their_scope():
    """On the splash path (the kernel interpreted on the CPU) the kernel's
    call sits under ``dopt_splash`` inside ``dopt_attn``, forward and
    backward, beside the other three parts; the queries' scale and cast
    in front of it under ``dopt_rope``."""
    from jax._src.config import enable_compilation_cache

    model = toy_model([2, 1], attn_block=128, head_dim=128,
                      num_key_value_heads=1, sliding_window=100)
    assert model.attention_path(256) == "splash"
    x = np.random.default_rng(7).integers(0, VOCAB, (2, 256)).astype(np.int32)
    y = np.concatenate([x[:, 1:], np.full((2, 1), -1, np.int32)], 1)
    w = np.ones(2, np.float32)
    params = model.init(jax.random.key(2))["params"]
    grad = jax.jit(jax.grad(lambda p: model.loss(p, x, y, w)[0]))
    with enable_compilation_cache(False):
        stacks = _stacks(grad.lower(params).compile().as_text())
    for scope in (*ANATOMY, "dopt_splash"):
        _check_scope(stacks, scope, "dopt_attn")


def _scope_free(monkeypatch):
    """Every ``jax.named_scope`` (a context or a decorator) names nothing
    from here on."""
    import contextlib

    @contextlib.contextmanager
    def bare(name):
        yield

    monkeypatch.setattr(jax, "named_scope", bare)


@pytest.mark.parametrize("model_type", ["laguna", "KeyeVL2", "joyai_llm_flash"])
def test_the_scopes_are_metadata_only(monkeypatch, model_type):
    """The round program lowers to the same text without debug info with
    every scope and without any: they name ops and change none."""
    from dopt.engine import GossipTrainer

    def lowered():
        return GossipTrainer(gossip_config(model_type),
                             eval_every=10**9).lower_round(1)[1]

    scoped = lowered()
    assert "dopt_qkv" in scoped.as_text(debug_info=True)
    _scope_free(monkeypatch)
    bare = lowered()
    assert "dopt_qkv" not in bare.as_text(debug_info=True)
    assert bare.as_text() == scoped.as_text()


@pytest.mark.parametrize("path", ["splash", "indexed-fused"])
def test_the_kernel_paths_scopes_are_metadata_only(monkeypatch, path):
    """... and so on the two kernels' paths, whose toys lower the
    interpreted kernels: the gradient program's text is the same with and
    without every scope.  (A model is built for each: it traces each
    layer kind's body once and keeps it.)"""
    def toy():
        if path == "splash":
            return toy_model([2, 1], attn_block=128, head_dim=128,
                             num_key_value_heads=1, sliding_window=100)
        return keye_model(2, attn_block=128, head_dim=128, topk=100,
                          rope_scaling={"mrope_section": [16, 24, 24],
                                        "rope_type": "default",
                                        "type": "default"})

    assert toy().attention_path(256) == path
    x = np.random.default_rng(7).integers(0, VOCAB, (2, 256)).astype(np.int32)
    y = np.concatenate([x[:, 1:], np.full((2, 1), -1, np.int32)], 1)
    w = np.ones(2, np.float32)
    params = toy().init(jax.random.key(2))["params"]

    def lowered():
        model = toy()
        return jax.jit(jax.grad(lambda p: model.loss(p, x, y, w)[0])
                       ).lower(params)

    scoped = lowered()
    assert "dopt_oproj" in scoped.as_text(debug_info=True)
    _scope_free(monkeypatch)
    bare = lowered()
    assert "dopt_oproj" not in bare.as_text(debug_info=True)
    assert bare.as_text() == scoped.as_text()


def test_the_federated_engine_refuses_the_decoder_with_a_pointer():
    from dopt.config import FederatedConfig
    from dopt.engine import FederatedTrainer

    cfg = gossip_config()
    with pytest.raises(ValueError, match="gossip engine"):
        FederatedTrainer(cfg.replace(gossip=None,
                                     federated=FederatedConfig()))


# ------------------------------------------ what a layer's checkpoint keeps

def _grad_program(name):
    """(jitted gradient of the toy's loss, its parameters) for a pattern."""
    model, _, (x, y, w), *_ = case(name)
    params = model.init(jax.random.key(4))["params"]
    return jax.jit(jax.grad(lambda p: model.loss(p, x, y, w)[0])), params


def _recomputed_matmuls(grad, params):
    """Name stacks of the compiled ``dot`` / ``convolution`` instructions
    under ``rematted_computation``, jax's name for a checkpoint's
    recompute, but those of the head block and of the attention block's
    OWN checkpoint (the scope then stands before the recompute's name,
    with the ``lax.map`` over a run of blocks between them in the indexed
    body: the ``jax.numpy`` blocks never hold their scores, which is what
    the fused kernel's backward does inside itself)."""
    import re

    from jax._src.config import enable_compilation_cache

    with enable_compilation_cache(False):   # the key ignores metadata
        text = grad.lower(params).compile().as_text()
    found = re.findall(r'= \S+ (?:dot|convolution)\(.*op_name="([^"]*)"', text)
    assert any("rematted_computation" not in s for s in found)
    own = re.compile(r"dopt_attn/(while/body/closed_call/)?checkpoint/"
                     r"rematted_computation")
    return [s for s in found if "rematted_computation" in s
            and "dopt_head" not in s and not own.search(s)]


@pytest.mark.parametrize("pattern", ALL_PATTERNS)
def test_a_layer_recomputes_no_matmul(pattern):
    """A count, on the CPU: the backward pass computes none of a layer's
    matmuls again: q / k / v, the per-head gate, the output projection,
    the router, and the dense, the shared and the held experts' gate and
    up products are all kept (the down projections feed nothing the
    backward pass needs)."""
    assert _recomputed_matmuls(*_grad_program(pattern)) == []


def test_the_count_sees_what_a_bare_policy_recomputes(monkeypatch):
    """The reader of the count above, held to the policy of before: with
    the kernel's residuals alone kept, the layer's recompute has the
    q / k / v, output, expert and MLP projections in it."""
    from dopt.models import decoder

    monkeypatch.setattr(decoder, "LAYER_KEEPS", (decoder.ATTN_RESIDUALS,))
    stacks = _recomputed_matmuls(*_grad_program("published-pattern"))
    for name in ("td,dne->nte", "td,dn->nt", "nte,ned->td", "epd,edf->pf",
                 "dopt_route"):
        assert any(name in s for s in stacks), name


@pytest.mark.parametrize("pattern", ALL_PATTERNS)
def test_gradients_equal_those_without_any_checkpoint(monkeypatch, pattern):
    """The kept values are the values the recompute would produce: every
    gradient leaf equals that of the same layers with ``jax.checkpoint``
    the identity (layer, head block and attention block) to 1e-6 of the
    leaf's largest value (5e-6 for the sparse attention: the alignment
    term's gradient is the difference of two distributions, softmax of
    the index scores less the attention's head-mean, and what XLA fuses
    differently on the two sides is not cancelled; 1.07e-6 was read)."""
    from dopt.models import decoder

    tol = 5e-6 if pattern in KEYE_PATTERNS else 1e-6
    grad, params = _grad_program(pattern)
    got = grad(params)
    assert "prevent_cse" in str(jax.make_jaxpr(grad)(params))
    monkeypatch.setattr(jax, "checkpoint", lambda f, **kw: f)
    monkeypatch.setattr(decoder, "_attend_block",
                        decoder._attend_block.__wrapped__)
    plain, _ = _grad_program(pattern)
    assert "prevent_cse" not in str(jax.make_jaxpr(plain)(params))
    bad = {jax.tree_util.keystr(k) for (k, g), w in zip(
        jax.tree_util.tree_leaves_with_path(got),
        jax.tree.leaves(plain(params)))
        if not np.abs(g - w).max() <= tol * np.abs(w).max()}
    assert not bad


# ------------------------------------------------ the fused attention kernel

@pytest.mark.parametrize("window", [None, 100])
def test_splash_attention_equals_blocked_attention(window):
    """The fused kernel (jax's Pallas TPU splash attention, interpreted
    on the CPU) against the ``jax.numpy`` blocks: outputs and gradients, a
    causal and a banded mask, 2 query heads on each of 2 key/value heads."""
    from dopt.models.decoder import splash_causal_attention

    rng = np.random.default_rng(5)
    t, d = 256, 128
    q = jnp.asarray(rng.standard_normal((2, 2, t, d)).astype(np.float32))
    k, v = (jnp.asarray(rng.standard_normal((2, t, d)).astype(np.float32))
            for _ in range(2))
    w = jnp.asarray(rng.standard_normal((2, 2, t, d)).astype(np.float32))

    def blocked(q, k, v):
        return blocked_causal_attention(q, k, v, window=window, block=128)

    def splash(q, k, v):
        return splash_causal_attention(q / np.sqrt(d), k, v, window=window,
                                       block=128)

    assert close(splash(q, k, v), blocked(q, k, v))
    got, want = (jax.grad(lambda *a: jnp.sum(f(*a) * w), argnums=(0, 1, 2))(
        q, k, v) for f in (splash, blocked))
    assert all(close(g, x) for g, x in zip(got, want))


def test_decoder_takes_the_kernel_where_the_shapes_fit():
    """Only the kernel's shape limits choose between the two bodies of
    ``causal_attention``: the benchmark's cell (4,096 positions, heads of
    128, the module's own block) takes the kernel, and so does every
    layer of the preset and of the benchmark's configuration file.  At
    256 positions and heads of 128 a worker with blocks of 128 takes it
    and one with blocks of 64 (not a multiple of the chip's 128 lanes)
    ``jax.numpy``: the loss and every gradient leaf agree."""
    from dopt.models.decoder import attention_path
    from dopt.presets import get_preset

    assert attention_path(4096, 128) == "splash"
    assert attention_path(256, 128, 128) == "splash"
    for t, d, block in [(256, 128, 64), (384, 128, 128), (256, 8, 128),
                        (21, 8, 8), (48, 128, 512)]:
        assert attention_path(t, d, block) == "blocked"
    for model in (get_preset("laguna-localsgd2").model,
                  ModelConfig(**PUBLISHED["model"])):
        assert attention_path(model.input_shape[0],
                              model.decoder.head_dim) == "splash"
    kw = dict(head_dim=128, num_key_value_heads=1, sliding_window=100)
    x = np.random.default_rng(7).integers(0, VOCAB, (2, 256)).astype(np.int32)
    y = np.concatenate([x[:, 1:], np.full((2, 1), -1, np.int32)], 1)
    w = np.ones(2, np.float32)
    blocked, splash = (toy_model([2, 1], attn_block=block, **kw)
                       for block in (64, 128))
    params = blocked.init(jax.random.key(2))["params"]
    (want, _), want_g = jax.value_and_grad(
        lambda p: blocked.loss(p, x, y, w), has_aux=True)(params)
    (got, _), got_g = jax.value_and_grad(
        lambda p: splash.loss(p, x, y, w), has_aux=True)(params)
    assert abs(float(got) - float(want)) <= RTOL * float(want)
    assert all(jax.tree.leaves(jax.tree.map(close, got_g, want_g)))


# ------------------------------------- the sparse attention's fused kernels

# name: (first query's position, keys, keys kept a query, levels of the
# index scores: few levels plant ties at the threshold)
MASKS = {
    "first-block-keeps-every-visible-key": (0, 128, 128, None),
    "a-single-key-a-row": (256, 384, 1, None),
    "ties-at-the-threshold": (128, 256, 40, 5),
    "a-late-block-two-tiles-of-keys": (896, 1024, 100, None),
}


@pytest.mark.parametrize("mask", sorted(MASKS))
def test_sparse_attention_kernels_equal_the_jax_numpy_body(mask):
    """The three Pallas kernels (interpreted on the CPU) against the
    ``jax.numpy`` body (``decoder._masked_attention``) for masks that come from the selection: output,
    head-mean target, dq, dk and dv, 2 query heads on each of 2 key/value
    heads, float32 (so only the order of the arithmetic differs)."""
    from dopt.models.decoder import _masked_attention
    from dopt.ops.sparse_attention import masked_attention

    first, tk, top, levels = MASKS[mask]
    rng = np.random.default_rng(11)
    tq, d = 128, 128
    q = jnp.asarray(rng.standard_normal((2, 2, tq, d)).astype(np.float32))
    k, v = (jnp.asarray(rng.standard_normal((2, tk, d)).astype(np.float32))
            for _ in range(2))
    w = jnp.asarray(rng.standard_normal((2, 2, tq, d)).astype(np.float32))
    index = rng.standard_normal((tq, tk)).astype(np.float32)
    if levels:
        index = np.round(index * levels / 4) * 4 / levels
    at = first + np.arange(tq)
    seen = jnp.asarray(np.arange(tk)[None, :] <= at[:, None])
    keep = select_top_keys(jnp.asarray(index), seen,
                           jnp.minimum(at + 1, top))
    kept = np.asarray(keep).sum(-1)
    assert (kept == np.minimum(at + 1, top)).all()
    if levels:      # the threshold is tied in most rows: that is the case
        edge = np.where(np.asarray(keep), index, np.inf).min(-1)
        assert ((np.asarray(seen) & (index == edge[:, None])).sum(-1)
                > 1).mean() > 0.5

    def fused(q, k, v):
        return masked_attention(q, k, v, keep, first)

    def plain(q, k, v):
        return _masked_attention(q, k, v, keep)

    for got, want in zip(fused(q, k, v), plain(q, k, v)):
        assert close(got, want)
    got, want = (jax.grad(lambda *a: jnp.sum(f(*a)[0] * w),
                          argnums=(0, 1, 2))(q, k, v)
                 for f in (fused, plain))
    # (held to the largest of the three: where a row keeps one key, dq
    # and dk are zero and the kernel's are a float32 rounding of zero)
    scale = max(float(jnp.abs(x).max()) for x in want)
    assert all(float(jnp.abs(g - x).max()) <= RTOL * scale
               for g, x in zip(got, want))


def test_sparse_decoder_takes_the_kernels_where_the_shapes_fit():
    """Only the kernels' shape limits choose the body of the indexed
    attention: rows that are whole blocks, a block and a head that are
    multiples of the chip's 128 lanes -- the benchmark's cell, the preset
    and the configuration file take the kernels, the toys and the
    rehearsal's 48 positions ``jax.numpy``.  At 256 positions and heads of
    128 a worker with blocks of 128 takes them and one with blocks of 64
    does not: the loss, both counters and every gradient leaf agree, and
    the backward pass runs the forward kernel no second time (a block's
    recompute is the index scores, the selection and the head-mean)."""
    from dopt.models.decoder import indexed_attention_path
    from dopt.ops.sparse_attention import INDEX_KERNEL_NAMES, KERNEL_NAMES
    from dopt.presets import get_preset

    assert indexed_attention_path(8192, 128, 16) == "indexed-fused"
    assert indexed_attention_path(256, 128, 4, 128) == "indexed-fused"
    assert indexed_attention_path(256, 128, 32, 128) == "indexed-fused"
    for t, d, j, block in [(256, 128, 4, 64), (384, 128, 4, 256),
                           (256, 8, 4, 128), (48, 8, 4, 4), (48, 128, 16, 256),
                           (8192 + 128, 128, 16, 256),
                           # the index kernels hold every indexer head's
                           # queries of a block side by side: 4,096 lanes
                           (8192, 128, 17, 256), (256, 128, 33, 128)]:
        assert indexed_attention_path(t, d, j, block) == "indexed"
    for model in (get_preset("keye-localsgd2").model,
                  ModelConfig(**KEYE["model"])):
        worker = GatedMoEDecoder(model.decoder, vocab_rows=VOCAB)
        assert worker.attention_path(model.input_shape[0]) == "indexed-fused"
    rehearsal = KEYE["rehearsal"]["model"]["input_shape"][0]
    assert worker.attention_path(rehearsal) == "indexed"
    assert keye_model().attention_path(KEYE_T) == "indexed"

    kw = dict(head_dim=128, topk=100, rope_scaling={
        "mrope_section": [16, 24, 24], "rope_type": "default",
        "type": "default"})
    x = np.random.default_rng(7).integers(0, VOCAB, (2, 256)).astype(np.int32)
    y = np.concatenate([x[:, 1:], np.full((2, 1), -1, np.int32)], 1)
    w = np.ones(2, np.float32)
    plain, fused = (keye_model(2, attn_block=block, **kw)
                    for block in (64, 128))
    assert (plain.attention_path(256), fused.attention_path(256)) == (
        "indexed", "indexed-fused")
    params = plain.init(jax.random.key(2))["params"]
    (want, want_aux), want_g = jax.value_and_grad(
        lambda p: plain.loss(p, x, y, w), has_aux=True)(params)
    (got, got_aux), got_g = jax.value_and_grad(
        lambda p: fused.loss(p, x, y, w), has_aux=True)(params)
    assert abs(float(got) - float(want)) <= RTOL * float(want)
    for name in ("index_align_loss", "index_keys_kept_share"):
        assert abs(float(got_aux[name]) - float(want_aux[name])) <= (
            RTOL * float(want_aux[name]))
    assert all(jax.tree.leaves(jax.tree.map(close, got_g, want_g)))
    # per layer: forward, head-mean (and again in the recompute), backward
    from jax._src.core import jaxprs_in_params

    calls = {name: 0 for name in (*KERNEL_NAMES.values(),
                                  *INDEX_KERNEL_NAMES.values())}

    def count(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                calls[eqn.params["name"]] += 1
            for inner in jaxprs_in_params(eqn.params):
                count(inner)

    count(jax.make_jaxpr(
        jax.grad(lambda p: fused.loss(p, x, y, w)[0]))(params).jaxpr)
    # ... and the index scores: forward (and again in the recompute),
    # backward
    assert calls == {**dict(zip(KERNEL_NAMES.values(), (2, 4, 2))),
                     **dict(zip(INDEX_KERNEL_NAMES.values(), (4, 2)))}


# --------------------------------------------- the index scores' two kernels

def _index_inputs(seed, t, heads=4, dim=8, lead=()):
    """(qi [*lead, J, T, E], ki [*lead, T, E], wi [*lead, T, J], a
    cotangent [*lead, T, T]) for a row of ``t`` positions."""
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.standard_normal((*lead, *shape)).astype(
        np.float32)) for shape in ((heads, t, dim), (t, dim), (t, heads),
                                   (t, t)))


def _block_scores(body, qi, ki, wi, weight, first, tq, keys):
    """sum(index * weight) over the keys the block's queries see, and the
    masked scores: what the decoder takes of a block's index scores."""
    at = first + jnp.arange(tq)
    seen = jnp.arange(keys)[None, :] <= at[:, None]
    index = jnp.where(seen, body(
        jax.lax.dynamic_slice_in_dim(qi, first, tq, 1), ki[:keys],
        jax.lax.dynamic_slice_in_dim(wi, first, tq, 0)), 0.0)
    cut = jax.lax.dynamic_slice_in_dim(weight, first, tq, 0)[:, :keys]
    return jnp.sum(index * cut), index


INDEX_BLOCKS = {"the-first-block": (0, 128), "the-first-block-of-a-run": (0, 512),
                "mid-row": (384, 512), "mid-row-shared-extent": (256, 1024)}


@pytest.mark.parametrize("how", ["alone", "two-vmaps", "map-checkpoint"])
@pytest.mark.parametrize("where", sorted(INDEX_BLOCKS))
def test_index_score_kernels_equal_the_jax_numpy_definition(where, how):
    """The two Pallas kernels of the index scores (interpreted on the
    CPU) against ``decoder._index_scores``: the scores a block's queries
    see and all three gradients, float32 (so only the order of the
    arithmetic differs), 4 indexer heads of 8, blocks of 128 queries
    against an extent of keys that ends with the block or runs past it
    (a run's blocks share the extent of its last one): alone, under the
    engines' two ``vmap``s (workers, rows), and a run of blocks through
    ``lax.map`` + ``jax.checkpoint`` as ``indexed_causal_attention``
    calls them."""
    from dopt.models.decoder import _index_scores
    from dopt.ops.sparse_attention import index_scores

    first, keys = INDEX_BLOCKS[where]
    tq, lead = 128, (2, 2) if how == "two-vmaps" else ()
    inputs = _index_inputs(13, 1024, lead=lead)

    def program(fused):
        def body(qi, ki, wi, first):
            return (index_scores(qi, ki, wi, first) if fused
                    else _index_scores(qi, ki, wi))

        def block(qi, ki, wi, weight, first):
            return _block_scores(
                lambda q, k, w: body(q, k, w, first), qi, ki, wi, weight,
                first, tq, keys)

        def run(qi, ki, wi, weight):
            if how != "map-checkpoint":
                return block(qi, ki, wi, weight, first)
            # every block of the run that ends at ``keys``
            total, index = jax.lax.map(
                lambda at: jax.checkpoint(block)(qi, ki, wi, weight, at),
                jnp.arange(keys - 4 * tq if keys > 4 * tq else 0, keys, tq))
            return jnp.sum(total), index

        for _ in lead:
            run = jax.vmap(run)
        return jax.value_and_grad(
            lambda *a: (lambda out: (jnp.sum(out[0]), out[1]))(run(*a)),
            argnums=(0, 1, 2), has_aux=True)

    # (the summed loss cancels to a thousandth of its terms: not compared)
    ((_, got_index), got_g), ((_, want_index), want_g) = (
        program(fused)(*inputs) for fused in (True, False))
    assert close(got_index, want_index)
    assert float(jnp.abs(want_index).max()) > 1.0
    for g, w in zip(got_g, want_g):
        assert float(jnp.abs(w).max()) > 0 and close(g, w)


def _one_indexed_block(seed, first, keys, tq=128, topk=40):
    """Inputs of ``decoder._indexed_block`` at the kernels' smallest
    shapes: 2 query heads on each of 2 key/value heads of 128, 4 indexer
    heads of 8."""
    rng = np.random.default_rng(seed)

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape).astype(np.float32))

    return (normal(2, 2, tq, 128), normal(2, keys, 128), normal(2, keys, 128),
            normal(4, tq, 8), normal(keys, 8), normal(tq, 4))


@pytest.mark.parametrize("poison", [float("nan"), 1e30])
def test_keys_past_a_block_s_last_tile_reach_nothing(poison):
    """What the fused block holds for the tiles of keys past its last
    query (a run's blocks share the extent of its last one) reaches no
    result: with NaN or 1e30 in every row of ``ki``, ``k`` and ``v`` past
    the block's last tile of 256 keys, the output, the alignment sum,
    the count of kept keys and all six gradients are those of clean
    rows, bit for bit, and the poisoned rows' own gradients are exact
    zeros.  (The index kernels write zeros there and take no gradient
    from there; the attention's visit no such tile either.)"""
    from dopt.models.decoder import _indexed_block

    first, keys, seen_to = 128, 768, 256
    q, k, v, qi, ki, wi = _one_indexed_block(17, first, keys)
    weight = jnp.asarray(np.random.default_rng(3).standard_normal(
        q.shape).astype(np.float32))

    def loss(*a):
        out, align, kept = _indexed_block(*a, first, topk=40, fused=True)
        return jnp.sum(out * weight) + align, (out, align, kept)

    grad = jax.value_and_grad(loss, argnums=tuple(range(6)), has_aux=True)
    (_, want), want_g = grad(q, k, v, qi, ki, wi)
    k, v, ki = (x.at[..., seen_to:, :].set(poison) for x in (k, v, ki))
    (_, got), got_g = grad(q, k, v, qi, ki, wi)
    assert float(want[2]) == sum(min(first + i + 1, 40) for i in range(128))
    for g, w in zip((*got, *got_g), (*want, *want_g)):
        assert np.array_equal(np.asarray(g), np.asarray(w))
    for i in (1, 2, 4):
        assert not np.asarray(got_g[i][..., seen_to:, :]).any()
        assert np.asarray(got_g[i][..., :seen_to, :]).any()


def test_the_fused_row_is_the_jax_numpy_row(monkeypatch):
    """``indexed_causal_attention`` over a row of 512 positions in blocks
    of 128 (one run: 40 keys a query, so every block but the first rows
    of the first selects), the kernels against the ``jax.numpy`` bodies
    at the SAME blocks: the output, the alignment term, the kept share,
    and the gradients of a loss with both terms on all six inputs (the
    indexer's three get the alignment term's alone).  The two selections
    are the same masks: where a block's two index scores differ it is by
    float32 rounding (under 1e-5 of the largest score), and a key chosen
    by one body alone would sit that close to its query's cut."""
    from dopt.models import decoder
    from dopt.ops.sparse_attention import index_scores

    t, tq, topk = 512, 128, 40
    q, k, v, qi, ki, wi = _one_indexed_block(19, 0, t, tq=t)
    wi = wi / 8
    weight = jnp.asarray(np.random.default_rng(5).standard_normal(
        q.shape).astype(np.float32))

    def loss(*a):
        out, align, kept = indexed_causal_attention(*a, topk=topk, block=tq)
        return jnp.sum(out * weight) + 100.0 * align, (out, align, kept)

    grad = jax.value_and_grad(loss, argnums=tuple(range(6)), has_aux=True)
    assert decoder.indexed_attention_path(t, 128, 4, tq) == "indexed-fused"
    (_, got), got_g = grad(q, k, v, qi, ki, wi)
    monkeypatch.setattr(decoder, "indexed_attention_path",
                        lambda *a: "indexed")
    (_, want), want_g = grad(q, k, v, qi, ki, wi)
    assert float(want[1]) > 0 and float(want[2]) < 0.2
    for g, w in zip((*got, *got_g), (*want, *want_g)):
        assert close(g, w)
    for first in range(0, t, tq):
        at = first + jnp.arange(tq)
        seen = jnp.arange(t)[None, :] <= at[:, None]
        block = (qi[:, first:first + tq], ki, wi[first:first + tq])
        fused, plain = index_scores(*block, first), decoder._index_scores(
            *block)
        tol = 1e-5 * float(jnp.abs(plain).max())
        assert float(jnp.abs(jnp.where(seen, fused - plain, 0)).max()) <= tol
        count = jnp.minimum(at + 1, topk)
        a, b = (np.asarray(select_top_keys(x, seen, count))
                for x in (fused, plain))
        cut = np.where(b, np.asarray(plain), np.inf).min(-1, keepdims=True)
        assert (np.abs(np.asarray(plain) - cut)[a != b] <= tol).all()
        assert (a.sum(-1) == np.asarray(count)).all()


@pytest.fixture(scope="module")
def v5e_chip():
    """One chip of a DESCRIBED v5e (nothing is attached: the TPU's
    compiler is installed here and compiles for it).  Only this file of
    the suite loads the TPU's library, and only inside this fixture."""
    import os

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("precision", ["default", "highest"])
@pytest.mark.parametrize("heads, window", [(6, None), (8, 512)])
def test_splash_kernels_compile_for_the_chip_at_any_ambient_precision(
        v5e_chip, monkeypatch, precision, heads, window):
    """The forward and backward attention kernels at the published sizes
    (4,096 positions, 8 key/value heads of 128, 48 / 64 query heads)
    through the real XLA:TPU + Mosaic compile.  ``highest`` is what the
    benchmark's parity check sets around the whole program: Mosaic
    refuses bfloat16 operands at that precision, so the kernels pin their
    own (found on the chip, PERF.md PR 28).  Compile only: nothing runs."""
    from dopt.models.decoder import splash_causal_attention

    monkeypatch.setattr("dopt.ops.pallas_interpret", lambda: False)

    def loss(q, k, v):
        out = splash_causal_attention(q, k, v, window=window, block=512)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    def shape(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.bfloat16, sharding=v5e_chip)

    with jax.default_matmul_precision(precision):
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            shape(8, heads, 4096, 128), shape(8, 4096, 128),
            shape(8, 4096, 128)).compile()
    text = compiled.as_text()
    assert "splash_mqa_fwd" in text and "splash_mqa_dkv" in text


@pytest.mark.parametrize("precision", ["default", "highest"])
def test_sparse_attention_kernels_compile_for_the_chip_at_any_ambient_precision(
        v5e_chip, monkeypatch, precision):
    """Forward and gradient of the three kernels at the benchmark cell's
    sizes (4 x 8 heads of 128, a block of 256 queries against 8,192 keys,
    two workers under a ``vmap`` as the engines stack them) through the
    real XLA:TPU + Mosaic compile, also at the ``highest`` the parity
    check sets around the whole program.  The compiled custom calls'
    names carry both scopes they stand for: the benchmark's readers find
    them by ``dopt_attn`` and ``dopt_attend``.  Compile only: nothing
    runs."""
    import re

    from dopt.ops.sparse_attention import KERNEL_NAMES, masked_attention

    monkeypatch.setattr("dopt.ops.pallas_interpret", lambda: False)

    def loss(q, k, v, keep, first):
        out, target = masked_attention(q, k, v, keep, first,
                                       residual_name="attn_residuals")
        return jnp.sum(out.astype(jnp.float32) ** 2) + jnp.sum(target)

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=v5e_chip)

    step = jax.vmap(jax.value_and_grad(loss, argnums=(0, 1, 2)),
                    in_axes=(0, 0, 0, 0, None))
    with jax.default_matmul_precision(precision):
        compiled = jax.jit(step).lower(
            shape(2, 4, 8, 256, 128), shape(2, 4, 8192, 128),
            shape(2, 4, 8192, 128), shape(2, 256, 8192, dtype=jnp.bool_),
            shape(dtype=jnp.int32)).compile()
    # the instructions of the Mosaic custom calls, by the name a trace shows
    called = re.findall(
        r'%(\S+) = [^\n]*custom-call\([^\n]*custom_call_target="tpu_custom_call"',
        compiled.as_text())
    assert len(called) == 3
    for kernel in KERNEL_NAMES.values():
        assert sum(kernel in name for name in called) == 1, kernel
    assert all("dopt_attn" in name and "dopt_attend" in name
               for name in called)


@pytest.mark.parametrize("precision", ["default", "highest"])
def test_a_fused_run_compiles_for_the_chip_and_holds_no_index_product_in_hbm(
        v5e_chip, monkeypatch, precision):
    """One run of the indexed attention at the benchmark cell's sizes (4
    blocks of 256 queries against 1,024 keys of which a query keeps 512,
    4 x 8 heads of 128, 16 indexer heads of 64, two workers under a
    ``vmap``), forward and gradient through the real XLA:TPU + Mosaic
    compile, also at the ``highest`` the parity check sets around the
    whole program.  The index kernels go by names that carry
    ``dopt_attn`` and ``dopt_index`` and neither ``dopt_attend`` nor
    ``dopt_select`` (whose readers divide by the time under them), and so
    does their name stack; forward, recompute and backward are one call
    each; and no float32 array of [16, 256, keys] is left anywhere in
    the compiled program: the products exist in VMEM alone.  Compile
    only: nothing runs."""
    import re

    from dopt.ops.sparse_attention import INDEX_KERNEL_NAMES

    monkeypatch.setattr("dopt.ops.pallas_interpret", lambda: False)

    def loss(*a):
        out, align, _ = indexed_causal_attention(*a, topk=512)
        return jnp.sum(out.astype(jnp.float32) ** 2) + align

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct((2, *dims), dtype, sharding=v5e_chip)

    with jax.default_matmul_precision(precision):
        text = jax.jit(jax.vmap(jax.grad(loss, argnums=tuple(range(6))))).lower(
            shape(4, 8, 1024, 128), shape(4, 1024, 128), shape(4, 1024, 128),
            shape(16, 1024, 64), shape(1024, 64),
            shape(1024, 16, dtype=jnp.float32)).compile().as_text()
    called = dict(re.findall(
        r'%(\S+) = [^\n]*custom-call\([^\n]*custom_call_target="tpu_custom_call"'
        r'[^\n]*op_name="([^"]*)"', text))
    mine = {name: stack for name, stack in called.items()
            if "dopt_index" in name}
    fwd, bwd = (sorted(n for n in mine if kernel in n)
                for kernel in INDEX_KERNEL_NAMES.values())
    # (beside the attention's three: a gradient alone takes its head-mean
    # in the recompute only)
    assert (len(fwd), len(bwd)) == (2, 1) and len(called) == 3 + 3
    for name, stack in mine.items():
        for text_ in (name, stack):
            assert "dopt_attn" in text_ and "dopt_index" in text_
            assert "dopt_attend" not in text_ and "dopt_select" not in text_
    assert sum("rematted_computation" in mine[n] for n in fwd) == 1
    assert "transpose(jvp(" in mine[bwd[0]]
    assert not re.findall(r"f32\[(?:2,)?16,256,\d+\]", text)
    assert re.findall(r"f32\[2,256,1024\]", text)      # the scores' sum is


# ------------------------------- the held experts: a dropless grouped matmul

def dense_dispatch(model, p, m):
    """The dense dropless dispatch the grouped matmul took the place of
    (PR 34), the definition it is held to: every held expert multiplies
    every token, and a token's combine weight is zero where it was not
    routed.  Same router, same shared expert."""
    dt = model.dtype
    weight, _, counts = model._route(p["router"], m)
    x = m.astype(dt)
    e = p["experts"]
    g = jnp.einsum("td,edf->tef", x, e["gate"].astype(dt))
    u = jnp.einsum("td,edf->tef", x, e["up"].astype(dt))
    mid = jax.nn.silu(g) * u * weight[..., None].astype(dt)
    out = jnp.einsum("tef,efd->td", mid, e["down"].astype(dt),
                     preferred_element_type=jnp.float32)
    if "shared" in p:
        out = out + _gated_mlp(p["shared"], x, dt).astype(jnp.float32)
    return out, counts


# (hidden size, expert width) of the two bodies: the toy's, which takes the
# sorted slots in jax.numpy, and the smallest the kernels take (a float32
# row of whole (8, 128) tiles), interpreted on the CPU.
EXPERT_BODIES = {"grouped": (DIM, 16), "grouped-fused": (1024, 128)}
EXPERT_TOKENS = 150            # 128 + 22: a full group is not whole tiles
HELD, OFFSET = 4, 8


def expert_layer(model_type, body, routing, workers=2):
    """(model, the stacked parameters of ``workers`` expert layers, their
    inputs [workers, tokens, hidden]) with the routing PLANTED through the
    router: input feature 0 is a constant 5 and the router's row 0 holds
    +8 for the held experts every token is to choose and -8 for those no
    token is to choose (scores of +-40 before the sigmoid or softmax)."""
    hidden, width = EXPERT_BODIES[body]
    kw = dict(hidden_size=hidden, moe_intermediate_size=width, held=HELD,
              offset=OFFSET)
    model = (toy_model([4], dense_layers=0, **kw) if model_type == "laguna"
             else keye_model(1, **kw))
    assert model.expert_path() == body
    rng = np.random.default_rng(11)

    def mat(*shape):
        return (0.05 * rng.standard_normal((workers, *shape))
                ).astype(np.float32)

    p = {"router": mat(hidden, TOY["experts"]),
         "experts": {"gate": mat(HELD, hidden, width),
                     "up": mat(HELD, hidden, width),
                     "down": mat(HELD, width, hidden)}}
    if model_type == "laguna":
        p["shared"] = {"gate": mat(hidden, 16), "up": mat(hidden, 16),
                       "down": mat(16, hidden)}
    m = rng.standard_normal((workers, EXPERT_TOKENS, hidden)
                            ).astype(np.float32)
    held = slice(OFFSET, OFFSET + HELD)
    if routing != "router":
        m[:, :, 0] = 5.0
        p["router"][:, 0, held] = {"all": 8.0, "none": -8.0, "one": -8.0}[
            routing]
        if routing == "one":
            p["router"][:, 0, OFFSET + 1] = 8.0
    return model, jax.tree.map(jnp.asarray, p), jnp.asarray(m)


@pytest.mark.parametrize("routing", ["router", "all", "none", "one"])
@pytest.mark.parametrize("body", sorted(EXPERT_BODIES))
@pytest.mark.parametrize("model_type", ["laguna", "KeyeVL2"])
def test_grouped_experts_equal_the_dense_dispatch(model_type, body, routing):
    """The expert layer's output and EVERY gradient leaf (experts, router,
    shared expert, input) against the dense dispatch, to 2e-5 of the
    leaf's own largest value, under a ``vmap`` of two workers, for both
    bodies of the sorted slots: ``jax.numpy``, and the three kernels
    interpreted.  Routings: the router's own; every token to all held
    experts (the worst case: every slot of the arrays is filled, and a
    group of 150 is a whole tile and a part of one); no token to any
    held expert (zero slots: the routed part and the experts' gradients
    are exactly zero, nothing is NaN); all slots to one held expert."""
    model, p, m = expert_layer(model_type, body, routing)
    cot = jnp.asarray(np.random.default_rng(12).standard_normal(
        m.shape).astype(np.float32))

    def run(experts):
        def worker(p, m, cot):
            out, counts = experts(p, m)
            return jnp.sum(out * cot), (out, counts)

        return jax.jit(jax.vmap(jax.value_and_grad(
            worker, argnums=(0, 1), has_aux=True)))(p, m, cot)

    (_, (want, want_counts)), want_g = run(
        functools.partial(dense_dispatch, model))
    (_, (got, counts)), got_g = run(model._experts)
    share = {"router": None, "all": 1.0, "none": 0.0, "one": 0.25}[routing]
    if share is not None:
        assert np.allclose(counts["moe_held_slot_share"], share)
    for name in want_counts:
        assert np.array_equal(counts[name], want_counts[name]), name
    bad = {jax.tree_util.keystr(k) for (k, g), w in zip(
        jax.tree_util.tree_leaves_with_path((got, got_g)),
        jax.tree.leaves((want, want_g)))
        if not np.abs(g - w).max() <= RTOL * np.abs(w).max()}
    assert not bad
    assert all(np.isfinite(g).all() for g in jax.tree.leaves((got, got_g)))
    if routing == "none":
        shared = (_gated_mlp(jax.tree.map(lambda a: a[0], p["shared"]), m[0],
                             jnp.float32) if "shared" in p else 0.0)
        assert np.abs(got[0] - shared).max() <= RTOL * np.abs(m).max()
        assert all(not np.asarray(g).any()
                   for g in jax.tree.leaves(got_g[0]["experts"]))


@pytest.mark.parametrize("body", sorted(EXPERT_BODIES))
def test_rows_under_one_set_of_experts_share_the_grouped_matmul(body):
    """A ``vmap`` over rows whose experts are NOT batched (the model's own,
    over a worker's rows) inside one over workers: the rows' tokens go
    through one grouped matmul a worker and the experts' gradients come
    back summed over the rows, as the dense dispatch's do."""
    model, p, m = expert_layer("laguna", body, "router", workers=4)
    m = m.reshape(2, 2, *m.shape[1:])          # 2 workers x 2 rows
    p = jax.tree.map(lambda a: a[:2], p)

    def run(experts):
        def worker(p, m):
            out, _ = jax.vmap(lambda row: experts(p, row))(m)
            return jnp.sum(jnp.sin(out)), out

        return jax.jit(jax.vmap(jax.value_and_grad(
            worker, argnums=(0, 1), has_aux=True)))(p, m)

    want = run(functools.partial(dense_dispatch, model))
    got = run(model._experts)
    assert all(jax.tree.leaves(jax.tree.map(close, got, want)))


@pytest.mark.parametrize("model_type", ["laguna", "KeyeVL2"])
def test_the_router_gives_top_k_s_values_and_gradients_bit_for_bit(
        model_type):
    """The router takes its values at the chosen experts
    (``take_along_axis`` at ``top_k``'s indices, which a layer's
    checkpoint keeps) where it took ``top_k``'s own: the combine weights
    and their gradients to the router and the input are the same bits."""
    model, p, m = expert_layer(model_type, "grouped", "router", workers=1)
    p, m = jax.tree.map(lambda a: a[0], (p, m))
    c = model.cfg

    def before(router, m):
        scores = jnp.dot(m, router, precision=jax.lax.Precision.HIGHEST)
        scores = (jax.nn.softmax(scores, axis=-1) if c.indexed
                  else jax.nn.sigmoid(scores))
        top, idx = jax.lax.top_k(scores, c.num_experts_per_tok)
        top = top / jnp.sum(top, -1, keepdims=True)
        if not c.indexed:
            top = top * c.moe_routed_scaling_factor
        hit = ((idx - c.expert_offset)[..., None]
               == jnp.arange(model.experts_held)).astype(jnp.float32)
        return jnp.sum(hit * top[..., None], axis=1)

    cot = jnp.asarray(np.random.default_rng(5).standard_normal(
        (EXPERT_TOKENS, HELD)).astype(np.float32))
    want = jax.value_and_grad(
        lambda r, m: jnp.sum(before(r, m) * cot), argnums=(0, 1))(
            p["router"], m)
    got = jax.value_and_grad(
        lambda r, m: jnp.sum(model._route(r, m)[0] * cot), argnums=(0, 1))(
            p["router"], m)
    assert np.any(np.asarray(got[1][0]))
    assert all(np.array_equal(g, w) for g, w in zip(
        jax.tree.leaves(got), jax.tree.leaves(want)))


@pytest.mark.parametrize("pattern", ["published-pattern", "keye-top8"])
def test_the_compiled_toy_holds_one_top_k_a_layer(pattern):
    """A count, on the CPU: the compiled gradient of the toy's loss holds
    one ``top_k`` an expert layer (the forward's), where it held two (the
    layer's recompute ran it again for its indices; PERF.md, PR 29)."""
    grad, params = _grad_program(pattern)
    text = grad.lower(params).compile().as_text()
    layers = sum("router" in params[k] for k in params if "layer" in k)
    assert layers >= 3
    assert text.count('custom_call_target="TopK"') == layers


def test_the_experts_body_goes_by_shapes_alone():
    """Only the kernels' shape limits choose the held experts' body: a
    token's float32 row whole (8, 128) tiles and the expert's width whole
    lanes -- both benchmark cells, their presets and configuration files
    take the kernels, the toys and the rehearsals ``jax.numpy``; and
    ``python -m dopt.run`` prints which."""
    from dopt.ops.grouped_experts import path, slot_capacity
    from dopt.presets import get_preset
    from dopt.run import device_details

    assert path(2048, 512) == path(2048, 768) == "grouped-fused"
    assert path(1024, 128) == "grouped-fused"
    for hidden, width in [(DIM, 16), (1024 + 128, 128), (1024, 100),
                          (512, 128), (64, 64)]:
        assert path(hidden, width) == "grouped"
    for preset, config in (("laguna-localsgd2", PUBLISHED),
                           ("keye-localsgd2", KEYE)):
        for model in (get_preset(preset).model,
                      ModelConfig(**config["model"])):
            worker = GatedMoEDecoder(model.decoder, vocab_rows=VOCAB)
            assert worker.expert_path() == "grouped-fused"
        toy = ModelConfig(**{**config["model"],
                             **config["rehearsal"]["model"]})
        assert GatedMoEDecoder(toy.decoder, vocab_rows=VOCAB
                               ).expert_path() == "grouped"
        assert GatedMoEDecoder(get_preset(preset + "-toy").model.decoder,
                               vocab_rows=VOCAB).expert_path() == "grouped"
    # the worst case: every token chooses min(k, held) held experts, and
    # every group's last tile may be a part of one
    assert slot_capacity(4096, 8, 8) == 4096 * 8 // 128 + 8
    assert slot_capacity(150, 4, 8) == 5 + 8
    from dopt.engine import GossipTrainer

    line = device_details(GossipTrainer(gossip_config(), eval_every=10**9))
    assert line.endswith(
        "attention=blocked rope=jnp experts=grouped layers=3 kinds=2")


@pytest.mark.parametrize("precision", ["default", "highest"])
@pytest.mark.parametrize("tokens, width, published", [
    (4096, 512, 256), (8192, 768, 128)])
def test_grouped_expert_kernels_compile_for_the_chip_at_any_ambient_precision(
        v5e_chip, monkeypatch, precision, tokens, width, published):
    """Forward and gradient of the held experts at the two benchmark
    cells' sizes (8 held experts of 2,048 x ``width``, rows of 4,096 and
    8,192 tokens, two workers under the engines' ``vmap`` and a row under
    the model's) through the real XLA:TPU + Mosaic compile, also at the
    ``highest`` the parity check sets around the whole program: ONE call
    of each of the three kernels serves both workers (``custom_vmap``
    folds them into the groups), and the compiled custom calls' names
    carry the scope they stand for, by which the benchmark's readers
    find them.  Compile only: nothing runs."""
    import re

    from dopt.ops.grouped_experts import KERNEL_NAMES, grouped_experts

    monkeypatch.setattr("dopt.ops.pallas_interpret", lambda: False)

    def loss(m, hit, weight, experts):
        out = jax.vmap(lambda m, hit, weight: grouped_experts(
            m, hit, weight, experts, jnp.zeros_like(m), k=8,
            dtype=jnp.bfloat16, keep_name="matmul_products"))(m, hit, weight)
        return jnp.sum(out ** 2)

    def shape(*dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct((2, *dims), dtype, sharding=v5e_chip)

    experts = {"gate": shape(8, 2048, width), "up": shape(8, 2048, width),
               "down": shape(8, width, 2048)}
    with jax.default_matmul_precision(precision):
        compiled = jax.jit(jax.vmap(jax.grad(loss, argnums=(0, 2, 3)))).lower(
            shape(1, tokens, 2048), shape(1, tokens, 8, dtype=jnp.bool_),
            shape(1, tokens, 8), experts).compile()
    called = re.findall(
        r'%(\S+) = [^\n]*custom-call\([^\n]*custom_call_target="tpu_custom_call"',
        compiled.as_text())
    assert len(called) == 3
    for kernel in KERNEL_NAMES.values():
        assert sum(kernel in name for name in called) == 1, kernel
    assert all("dopt_moe" in name for name in called)


# ------------- latent attention, the corrected router and the MTP module

@pytest.mark.parametrize("dims", [(192, 128), (64, 128)])
def test_splash_attention_takes_values_narrower_than_its_keys(dims):
    """The fused kernel (interpreted on the CPU) with query and key heads
    wider or narrower than the value heads, as latent attention has them
    (192 over 128), against the ``jax.numpy`` blocks: outputs and
    gradients, one query head on each of 2 key/value heads."""
    from dopt.models.decoder import attention_path, splash_causal_attention

    qk, vd = dims
    rng = np.random.default_rng(11)
    t = 256
    q = jnp.asarray(rng.standard_normal((2, 1, t, qk)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((2, t, qk)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((2, t, vd)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((2, 1, t, vd)).astype(np.float32))
    assert attention_path(t, qk, 128, vd) == "splash"

    def blocked(q, k, v):
        return blocked_causal_attention(q, k, v, window=None, block=128)

    def splash(q, k, v):
        return splash_causal_attention(q / np.sqrt(qk), k, v, window=None,
                                       block=128)

    assert splash(q, k, v).shape == (2, 1, t, vd)
    assert close(splash(q, k, v), blocked(q, k, v))
    got, want = (jax.grad(lambda *a: jnp.sum(f(*a) * w), argnums=(0, 1, 2))(
        q, k, v) for f in (splash, blocked))
    assert all(close(g, x) for g, x in zip(got, want))


def test_the_latent_attention_takes_the_kernel_at_the_cell_s_shapes():
    """The cell's shapes (4,096 positions, heads of 192 over 128) are the
    kernel's, in the preset and in the configuration file alike; a toy
    worker with rows of 256, blocks of 128 and the published heads takes
    it too, and its loss and every gradient leaf agree with the same
    worker in ``jax.numpy`` blocks of 64."""
    from dopt.models.decoder import attention_path
    from dopt.presets import get_preset

    assert attention_path(4096, 192, 512, 128) == "splash"
    assert attention_path(4096, 192, 512, 96) == "blocked"
    assert attention_path(4096, 160, 512, 128) == "blocked"
    for model in (get_preset("joyai-localsgd2").model,
                  ModelConfig(**JOYAI["model"])):
        worker = GatedMoEDecoder(model.decoder, vocab_rows=VOCAB)
        assert worker.attention_path(model.input_shape[0]) == "splash"
    kw = dict(num_attention_heads=1, num_key_value_heads=1, head_dim=64,
              qk_rope_head_dim=64, qk_nope_head_dim=128, qk_head_dim=192,
              v_head_dim=128)
    blocked, splash = (joyai_model(2, attn_block=block, **kw)
                       for block in (64, 128))
    assert (blocked.attention_path(256), splash.attention_path(256)) == (
        "blocked", "splash")
    x = np.random.default_rng(7).integers(0, VOCAB, (2, 256)).astype(np.int32)
    y = np.concatenate([x[:, 1:], np.full((2, 1), -1, np.int32)], 1)
    w = np.ones(2, np.float32)
    params = blocked.init(jax.random.key(2))["params"]
    (want, _), want_g = jax.value_and_grad(
        lambda p: blocked.loss(p, x, y, w), has_aux=True)(params)
    (got, _), got_g = jax.value_and_grad(
        lambda p: splash.loss(p, x, y, w), has_aux=True)(params)
    assert abs(float(got) - float(want)) <= RTOL * float(want)
    assert all(jax.tree.leaves(jax.tree.map(close, got_g, want_g)))


def test_the_rotary_interleaves_as_the_reference_does():
    """``_rotary`` with ``interleave`` is the pair rotation (2i, 2i + 1)
    in another order of the dimensions, the same for every vector: every
    dot product of a rotated query and a rotated key is the reference's;
    ``rotate_half`` in its place gives other scores."""
    from dopt.models.decoder import _rotary

    rng = np.random.default_rng(3)
    q = rng.standard_normal((2, 17, 8)).astype(np.float32)
    k = rng.standard_normal((1, 17, 8)).astype(np.float32)
    spec = {"theta": 100.0}
    rope = {"rope_type": "default", "rope_theta": 100.0, "interleave": True}

    def scores(q, k):
        return np.einsum("hqd,hkd->hqk", np.asarray(q), np.asarray(k))

    want = scores(np.moveaxis(joy._rotary(np.moveaxis(q, 0, 1), spec), 1, 0),
                  np.moveaxis(joy._rotary(np.moveaxis(k, 0, 1), spec), 1, 0))
    got = scores(_rotary(q, rope), _rotary(k, rope))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    half = scores(_rotary(q, {**rope, "interleave": False}),
                  _rotary(k, {**rope, "interleave": False}))
    assert np.abs(half - want).max() > 0.1


# Workers at published head widths and rows of 256 (two layers of each
# kind the cell has), whose queries and keys take the rotary kernels:
# model type -> (model, its keys).
ROPE_WIDTHS = {
    "laguna": lambda block: toy_model(
        [2, 2], attn_block=block, head_dim=128, num_key_value_heads=1,
        sliding_window=100, full_every=2),
    "joyai_llm_flash": lambda block: joyai_model(
        2, attn_block=block, num_attention_heads=2, num_key_value_heads=2,
        head_dim=64, qk_rope_head_dim=64, qk_nope_head_dim=128,
        qk_head_dim=192, v_head_dim=128),
}


@pytest.mark.parametrize("block", [128, 64])
@pytest.mark.parametrize("model_type", sorted(ROPE_WIDTHS))
def test_the_rotary_kernels_leave_the_loss_and_gradients_as_rotary_does(
        monkeypatch, model_type, block):
    """One laguna worker (a full layer with YaRN over half its heads of
    128, a sliding one with the default rotary over the whole head) and
    one joyai worker (queries and keys of 128 + 64 interleaved rotary
    lanes over values of 128, two layers and the MTP block) at rows of
    256: every layer kind takes the rotary kernels (``rotary_path``), the
    traced program holds both, and the hidden state, the loss and every
    gradient leaf equal those of the same worker with ``_rotary`` forced.
    Blocks of 128 take the splash kernel, whose queries the rotary kernel
    scales; blocks of 64 the ``jax.numpy`` attention, which scales its
    scores.  The toys' 8-wide heads never reach the kernels."""
    from dopt.models import decoder

    x = np.random.default_rng(7).integers(0, VOCAB, (2, 256)).astype(np.int32)
    y = np.concatenate([x[:, 1:], np.full((2, 1), -1, np.int32)], 1)
    w = np.ones(2, np.float32)

    def run(model):
        def loss(p):
            return model.loss(p, x, y, w)

        (value, _), grads = jax.value_and_grad(loss, has_aux=True)(params)
        hidden = jax.vmap(lambda t: model._hidden(params, t)[0])(x)
        return value, grads, hidden, str(jax.make_jaxpr(
            jax.grad(lambda p: loss(p)[0]))(params))

    fused = ROPE_WIDTHS[model_type](block)
    assert fused.rotary_path(256) == "fused(2/2 kinds)"
    params = fused.init(jax.random.key(2))["params"]
    got, got_g, got_h, program = run(fused)
    assert "dopt_attn_dopt_rope_fwd" in program
    assert "dopt_attn_dopt_rope_bwd" in program
    monkeypatch.setattr(decoder, "rotary_path", lambda *a, **k: "jnp")
    plain = ROPE_WIDTHS[model_type](block)
    assert plain.rotary_path(256) == "jnp"
    want, want_g, want_h, program = run(plain)
    assert "dopt_attn_dopt_rope" not in program
    assert abs(float(got) - float(want)) <= RTOL * float(want)
    assert close(got_h, want_h)
    assert all(jax.tree.leaves(jax.tree.map(close, got_g, want_g)))


def test_the_correction_bias_chooses_and_does_not_weigh():
    """A nonzero ``router_bias`` changes which experts a token reaches
    but not the weights' formula (the sigmoid scores at the chosen
    experts, renormalised, times 2.5), and its gradient is exactly 0."""
    model = joyai_model(2, held=16)
    layer = joyai_params(2, 2, 16)["layer1"]
    m = jnp.asarray(np.random.default_rng(4).standard_normal(
        (T, DIM)).astype(np.float32))
    bias = jnp.asarray(np.random.default_rng(5).standard_normal(16),
                       jnp.float32)
    plain, _, _ = model._route(layer["router"], m, layer["router_bias"])
    moved, hit, _ = model._route(layer["router"], m, bias)
    assert (np.asarray(plain > 0) != np.asarray(hit)).any()
    scores = jax.nn.sigmoid(m @ layer["router"])
    top = np.where(np.asarray(hit), np.asarray(scores), 0.0)
    want = top / top.sum(-1, keepdims=True) * 2.5
    np.testing.assert_allclose(np.asarray(moved), want, rtol=1e-5, atol=1e-6)
    x, y, w = batch()
    params = joyai_params(0, 3, bias_scale=0.5)
    grads = jax.grad(lambda p: model_loss(joyai_model(3), p, x, y, w))(params)
    for name in ("layer1", "layer2"):
        assert float(jnp.abs(grads[name]["router_bias"]).max()) == 0.0
    assert float(jnp.abs(grads["mtp"]["layer"]["router_bias"]).max()) == 0.0


def model_loss(model, params, x, y, w):
    return model.loss(params, x, y, w)[0]


def test_the_mtp_term_counts_where_both_labels_count(monkeypatch):
    """The module's cross-entropy is taken where a position's label and
    the next label both count: a row's last two positions and the two
    around an uncounted label add nothing, nor does a padding row; it is
    the reference's second term alone, and a weight of 0 leaves the main
    cross-entropy, with no gradient on the module's own leaves."""
    from dopt.models import decoder

    x, y, w = batch()
    y[0, 5] = -1                   # positions 4 and 5 of row 0 drop out
    params = joyai_params(1, 2)
    _, aux = joyai_model(2).loss(params, x, y, w)
    spec = JOYAI_TOY
    after = np.concatenate([y[:, 1:], np.full_like(y[:, :1], -1)], 1)
    after = np.where(y >= 0, after, -1)
    assert (after[:, -2:] == -1).all() and (after[0, 4:6] == -1).all()
    mtp = jnp.stack([joy._mtp_logits(params, joy._hidden(params, r, spec),
                                     yr, spec) for r, yr in zip(x, y)])
    want = reference.token_cross_entropy(mtp, after, w)
    assert abs(float(aux["mtp_loss"]) - float(want)) <= RTOL * float(want)
    main = reference.token_cross_entropy(joy.forward(params, x, spec), y, w)
    monkeypatch.setattr(decoder, "MTP_LOSS_WEIGHT", 0.0)
    loss, grads = jax.value_and_grad(
        lambda p: model_loss(joyai_model(2), p, x, y, w))(params)
    assert abs(float(loss) - float(main)) <= RTOL * float(main)
    assert all(float(jnp.abs(g).max()) == 0.0
               for g in jax.tree.leaves(grads["mtp"]))


@pytest.mark.parametrize("change, message", [
    ({"n_group": 8, "topk_group": 4}, "group-limited"),
    ({"q_lora_rank": None}, "q_lora_rank is required"),
    ({"sa_config": KEYE["sa_config"]}, "a key of model_type 'KeyeVL2'"),
    ({"gating": True}, "a key of model_type 'laguna'"),
    ({"num_experts": 16}, "a key of model_type"),
    ({"scoring_func": "softmax"}, "sigmoid"),
    ({"topk_method": "greedy"}, "noaux_tc"),
    ({"rope_scaling": {"type": "yarn", "factor": 40}}, "unscaled"),
    ({"num_nextn_predict_layers": 2}, "0 or 1"),
    ({"moe_layer_freq": 2}, "moe_layer_freq"),
    ({"num_key_value_heads": 2}, "num_key_value_heads"),
    ({"qk_head_dim": 12}, "qk_head_dim"),
    ({"head_dim": 16}, "rotary key"),
    ({"norm_topk_prob": False}, "norm_topk_prob"),
    ({"attention_bias": True}, "no biases"),
])
def test_the_latent_attention_config_refuses(change, message):
    """What the layer cannot honour, a missing rank and a key of another
    model type are refused."""
    with pytest.raises(ValueError, match=message):
        joyai_decoder(**change)


def test_the_latent_attention_config_takes_every_published_key():
    """Every key of the published ``config.json`` (the configuration
    file's top level, at the published depth) builds the configuration,
    and what it says is read: MLA widths, one dense layer, the module."""
    from dopt.presets import joyai_llm_flash_decoder

    published = {k: v for k, v in JOYAI["model"]["decoder"].items()
                 if k not in ("experts_held", "expert_offset")}
    assert set(published) <= set(JOYAI)
    cfg = DecoderConfig(**{**published, "num_hidden_layers": 40})
    assert cfg == joyai_llm_flash_decoder()
    assert cfg.latent and cfg.mtp and cfg.experts == 256
    assert [cfg.sparse_mlp(i) for i in range(3)] == [False, True, True]
    assert cfg.routed_scale == 2.5 and cfg.shared_width == 768
    assert not joyai_decoder(num_nextn_predict_layers=0).mtp


def test_compiled_round_names_the_latent_and_mtp_parts():
    """``dopt_latent`` (the two down-projections and their norms) inside
    ``dopt_qkv``, forward and backward; ``dopt_mtp`` over the module: its
    projection, its block's attention and experts and its head pass, so
    ``head_ms`` holds both head passes."""
    from jax._src.config import enable_compilation_cache

    from dopt.engine import GossipTrainer

    _, lowered = GossipTrainer(gossip_config("joyai_llm_flash"),
                               eval_every=10**9).lower_round(1)
    with enable_compilation_cache(False):   # the key ignores metadata
        stacks = _stacks(lowered.compile().as_text())
    _check_scope(stacks, "dopt_latent", "dopt_qkv")
    _check_scope(stacks, "dopt_mtp", "dopt_local")
    mtp = {s for s in stacks if "dopt_mtp" in s}
    for part in ("dopt_attn", "dopt_moe", "dopt_head", "dopt_latent"):
        assert any(part in s for s in mtp), part
    assert any("dopt_attn" in s for s in stacks - mtp)


@pytest.mark.parametrize("precision", ["default", "highest"])
def test_latent_attention_kernels_compile_for_the_chip(
        v5e_chip, monkeypatch, precision):
    """The forward and backward splash kernels at the cell's sizes: 4,096
    positions, 32 heads with queries and keys of 192 and values of 128,
    through the real XLA:TPU + Mosaic compile, also at the parity
    check's ``highest``.  Compile only: nothing runs."""
    from dopt.models.decoder import splash_causal_attention

    monkeypatch.setattr("dopt.ops.pallas_interpret", lambda: False)

    def loss(q, k, v):
        out = splash_causal_attention(q, k, v, window=None, block=512)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    def shape(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.bfloat16, sharding=v5e_chip)

    with jax.default_matmul_precision(precision):
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            shape(32, 1, 4096, 192), shape(32, 4096, 192),
            shape(32, 4096, 128)).compile()
    text = compiled.as_text()
    assert "splash_mqa_fwd" in text and "splash_mqa_dkv" in text


# name: (heads, head lanes, rope parameters, lanes without rotary); the
# cells' shapes at 4,096 positions.
ROTARY_CELLS = {
    "laguna-full-queries": (48, 128, "full_attention", 0),
    "laguna-sliding-queries": (64, 128, "sliding_attention", 0),
    "laguna-keys": (8, 128, "full_attention", 0),
    "joyai-queries": (32, 192, None, 128),
}


@pytest.mark.parametrize("precision", ["default", "highest"])
@pytest.mark.parametrize("name", [*sorted(ROTARY_CELLS), "joyai-keys"])
def test_rotary_kernels_compile_for_the_chip(v5e_chip, monkeypatch, name,
                                             precision):
    """Forward and backward of both forms of the rotary kernel at the
    cells' sizes (4,096 positions; laguna's 48 / 64 query heads and 8 key
    heads of 128, YaRN over half of them or the default over the whole;
    joyai's 32 heads of 128 + 64 interleaved rotary lanes, and its keys
    and values of 32 heads from the kv product beside the shared key),
    two workers under a ``vmap`` as the engines stack them, through the
    real XLA:TPU + Mosaic compile, also at the parity check's
    ``highest``.  The compiled custom calls' names carry both scopes they
    stand for, ``dopt_attn`` and ``dopt_rope``.  Compile only: nothing
    runs."""
    from dopt.models.decoder import _tables, rotary_dim
    from dopt.ops import rotary

    monkeypatch.setattr("dopt.ops.pallas_interpret", lambda: False)

    def shape(*dims):
        return jax.ShapeDtypeStruct((2, *dims), jnp.float32,
                                    sharding=v5e_chip)

    def tables(rope, width):
        return _tables(rope, width, 4096)

    joyai = {"rope_type": "default", "rope_theta": JOYAI["rope_theta"],
             "interleave": True}
    if name == "joyai-keys":
        cos, sin = tables(joyai, 64)

        def loss(kv, shared):
            k, v = jax.vmap(lambda kv, r: rotary.latent_keys(
                kv, r, cos, sin, nope=128, interleave=True,
                dtype=jnp.bfloat16))(kv, shared)
            return jnp.sum(k.astype(jnp.float32) ** 2) + jnp.sum(
                v.astype(jnp.float32) ** 2)

        args = (shape(32, 4096, 256), shape(4096, 64))
    else:
        heads, d, kind, nope = ROTARY_CELLS[name]
        rope = (joyai if kind is None
                else PUBLISHED["model"]["decoder"]["rope_parameters"][kind])
        cos, sin = tables(rope, d - nope)

        def loss(x):
            y = jax.vmap(lambda x: rotary.rotate(
                x, cos, sin, rotary_dim=rotary_dim(rope, d - nope),
                interleave=kind is None, scale=d ** -0.5,
                dtype=jnp.bfloat16, nope=nope))(x)
            return jnp.sum(y.astype(jnp.float32) ** 2)

        args = (shape(heads, 4096, d),)
    with jax.default_matmul_precision(precision):
        text = jax.jit(jax.grad(loss, argnums=tuple(range(len(args))))).lower(
            *args).compile().as_text()
    assert "dopt_attn_dopt_rope_fwd" in text
    assert "dopt_attn_dopt_rope_bwd" in text
