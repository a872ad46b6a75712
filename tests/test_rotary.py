"""The rotary kernels (``dopt.ops.rotary``, interpreted on the CPU)
against their definition, ``dopt.models.decoder``'s ``_rotary`` with the
queries' scale and the cast to the compute dtype after it, at the
published head widths and short rows: laguna's heads of 128 (YaRN over
the first half with its attention factor, the default rotary over the
whole head), joyai's queries of 128 + 64 interleaved rotary lanes and
its keys and values from the kv up-projection beside ONE rotary key that
every head shares; and the rule that says where they run.

Tolerances.  Forward: the kernel folds the scale into its tables and
multiplies where the definition divides, so the float32 values differ
in their last bits and their roundings to bfloat16 by at most one unit
in the last place.  Backward: both sides are float32 from a bfloat16
cotangent, a few float32 roundings apart: 1e-6 of the largest value,
which a sum of the shared key's cotangents in bfloat16 (4e-3) does not
meet.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference_models import joyai_llm_flash as joy
from dopt.models import decoder
from dopt.models.decoder import _rotary, rotary_dim, rotary_path
from dopt.ops import rotary

BF16 = jnp.bfloat16
# rope_parameters of laguna's two layer kinds
# (benchmark/configs/laguna-xs2.json)
YARN = {"rope_theta": 500000, "rope_type": "yarn", "factor": 64,
        "original_max_position_embeddings": 4096, "beta_slow": 1,
        "beta_fast": 64, "attention_factor": 1.4158883083359672,
        "partial_rotary_factor": 0.5}
DEFAULT = {"rope_type": "default", "rope_theta": 10000,
           "partial_rotary_factor": 1}
# joyai's (c.rope(i) of benchmark/configs/joyai-llm-flash.json)
INTERLEAVED = {"rope_type": "default", "rope_theta": 32000000,
               "interleave": True}
T = 256
NOPE, ROPE, V = 128, 64, 128


def _normal(seed, *shape, dtype=jnp.float32):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(shape),
                       dtype)


def _ulps(a, b):
    """Units in the last place between two bfloat16 arrays, elementwise."""
    def ordered(x):
        i = np.asarray(x).view(np.int16).astype(np.int32)
        return np.where(i < 0, -32768 - i, i)

    return np.abs(ordered(a) - ordered(b))


def _interleaved_order(nope, rd):
    """The definition's lane of each of the kernel's lanes: ``_rotary``
    takes an interleaved rotary part apart (even lanes, then odd ones),
    the kernel keeps the product's order."""
    return np.concatenate([np.arange(nope), nope + np.arange(0, rd, 2),
                           nope + np.arange(1, rd, 2)])


def _definition(x, rope, nope, scaled):
    """The ``jax.numpy`` path: ``_rotary``, the scale, the cast."""
    rot = _rotary(x[..., nope:], rope)
    x = jnp.concatenate([x[..., :nope], rot], -1) if nope else rot
    return (x / math.sqrt(x.shape[-1]) if scaled else x).astype(BF16)


def _kernel(x, rope, nope, scaled, *, interleave=None):
    d = x.shape[-1]
    cos, sin = decoder._tables(rope, d - nope, x.shape[1])
    interleave = (bool(rope.get("interleave")) if interleave is None
                  else interleave)
    return rotary.rotate(x, cos, sin, rotary_dim=rotary_dim(rope, d - nope),
                         interleave=interleave,
                         scale=1 / math.sqrt(d) if scaled else 1.0,
                         dtype=BF16, nope=nope)


# name: (rope, heads, head lanes, lanes without rotary, scaled)
PLAIN = {
    "yarn-half-query": (YARN, 4, 128, 0, True),
    "yarn-half-key": (YARN, 2, 128, 0, False),
    "default-whole-query": (DEFAULT, 4, 128, 0, True),
    "default-whole-key": (DEFAULT, 2, 128, 0, False),
    "latent-query": (INTERLEAVED, 2, NOPE + ROPE, NOPE, True),
}


@pytest.mark.parametrize("name", sorted(PLAIN))
def test_rotate_is_rotary_scale_and_cast(name):
    """Forward to one bfloat16 ulp, the vector-Jacobian product to
    float32 rounding, of ``_rotary``, the scale and the cast, under the
    engines' ``vmap`` over two workers."""
    rope, heads, d, nope, scaled = PLAIN[name]
    x = _normal(1, 2, heads, T, d)
    order = _interleaved_order(nope, d - nope)
    if not rope.get("interleave"):
        order = np.arange(d)
    got_f = jax.vmap(lambda x: _kernel(x, rope, nope, scaled)[..., order])
    want_f = jax.vmap(lambda x: _definition(x, rope, nope, scaled))
    got, pull_got = jax.vjp(got_f, x)
    want, pull_want = jax.vjp(want_f, x)
    assert got.dtype == want.dtype == BF16
    assert _ulps(got, want).max() <= 1
    g = _normal(2, *want.shape, dtype=BF16)
    (dgot,), (dwant,) = pull_got(g), pull_want(g)
    np.testing.assert_allclose(dgot, dwant, rtol=0,
                               atol=1e-6 * np.abs(dwant).max())


def _latent_definition(kv, shared, heads):
    """The ``jax.numpy`` latent keys and values: the shared key rotated,
    beside each head's no-rotary lanes in float32, broadcast, then cast."""
    r = _rotary(shared[None], INTERLEAVED)
    k = jnp.concatenate([kv[..., :NOPE], jnp.broadcast_to(
        r, (heads, shared.shape[0], ROPE))], -1).astype(BF16)
    return k, kv[..., NOPE:].astype(BF16)


def _latent_kernel(kv, shared, interleave=True):
    cos, sin = decoder._tables(INTERLEAVED, ROPE, shared.shape[0])
    return rotary.latent_keys(kv, shared, cos, sin, nope=NOPE,
                              interleave=interleave, dtype=BF16)


@pytest.mark.parametrize("heads", [2, 32])
def test_latent_keys_are_the_jax_numpy_keys_and_values(heads):
    """k and v of a latent layer from the kv product and the shared rotary
    key, one pass: forward to one bfloat16 ulp (v exactly), and the
    backward's kv cotangent exactly, the shared key's (the heads' sum) to
    float32 rounding against ``jax.vjp`` of the ``jax.numpy`` path; 32
    heads is joyai's count, several blocks of heads on the grid."""
    kv = _normal(3, heads, T, NOPE + V)
    shared = _normal(4, T, ROPE)
    order = _interleaved_order(NOPE, ROPE)
    (k, v), pull_got = jax.vjp(
        lambda kv, r: (lambda k, v: (k[..., order], v))(
            *_latent_kernel(kv, r)), kv, shared)
    (k0, v0), pull_want = jax.vjp(
        lambda kv, r: _latent_definition(kv, r, heads), kv, shared)
    assert _ulps(k, k0).max() <= 1
    assert np.array_equal(np.asarray(v), np.asarray(v0))
    cot = (_normal(5, *k0.shape, dtype=BF16), _normal(6, *v0.shape,
                                                     dtype=BF16))
    (dkv, dr), (dkv0, dr0) = pull_got(cot), pull_want(cot)
    np.testing.assert_array_equal(dkv, dkv0)
    np.testing.assert_allclose(dr, dr0, rtol=0, atol=1e-6 * np.abs(dr0).max())
    # ... which a sum in bfloat16, a head at a time, does not meet
    rope_cot = cot[0][..., NOPE:]
    low = rope_cot[0]
    for h in range(1, heads):
        low = (low + rope_cot[h]).astype(BF16)
    high = jnp.sum(rope_cot.astype(jnp.float32), axis=0)
    assert np.abs(low.astype(jnp.float32) - high).max() > (
        1e-6 * np.abs(dr0).max())


def _scores_match(interleave: bool) -> bool:
    """Whether a latent layer's scores from the kernels (queries at 192,
    keys from the kv product and the shared key) equal the reference's
    interleaved definition, every query against every key of each head
    (float32 out of the kernels, so that the comparison is tight)."""
    heads, t = 2, 128
    spec, rope = {"theta": 100.0}, {**INTERLEAVED, "rope_theta": 100.0}
    q = _normal(7, heads, t, NOPE + ROPE)
    kv = _normal(8, heads, t, NOPE + V)
    shared = _normal(9, t, ROPE)
    cos, sin = decoder._tables(rope, ROPE, t)
    qk = rotary.rotate(q, cos, sin, rotary_dim=ROPE, interleave=interleave,
                       scale=1.0, dtype=jnp.float32, nope=NOPE)
    k, _ = rotary.latent_keys(kv, shared, cos, sin, nope=NOPE,
                              interleave=interleave, dtype=jnp.float32)
    got = np.einsum("hqd,hkd->hqk", qk, k)
    q_rot = np.moveaxis(joy._rotary(np.moveaxis(q[..., NOPE:], 0, 1), spec),
                        1, 0)
    k_rot = joy._rotary(shared, spec)
    want = (np.einsum("hqd,hkd->hqk", q[..., :NOPE], kv[..., :NOPE])
            + np.einsum("hqd,kd->hqk", q_rot, k_rot))
    return np.abs(got - want).max() <= 1e-4 * np.abs(want).max()


def test_the_kernels_score_the_interleaved_pairs_as_the_reference_does():
    """Joyai's interleaved pairs keep their meaning: every score of the
    kernels' queries and keys is the reference's interleaved definition;
    the kernels told to turn ``rotate_half`` pairs in their place give
    other scores, and the check sees it."""
    assert _scores_match(interleave=True)
    assert not _scores_match(interleave=False)


@pytest.mark.parametrize("t, head_dim, rotary_lanes, nope, normed, path", [
    (4096, 128, 64, 0, False, "fused"),      # laguna, full layers (YaRN)
    (4096, 128, 128, 0, False, "fused"),     # laguna, sliding layers
    (4096, 192, 64, 128, False, "fused"),    # joyai, latent q and k
    (256, 192, 64, 128, False, "fused"),     # the layer tests' short rows
    (8192, 128, 128, 0, True, "jnp"),        # keye: per-head norms first
    (8192, 64, 64, 0, False, "jnp"),         # keye's indexer: heads of 64
    (21, 8, 4, 0, False, "jnp"),             # toy rows and heads
    (4096, 8, 8, 0, False, "jnp"),           # toy heads, a long row
    (4000, 128, 128, 0, False, "jnp"),       # a row of partial tiles
    (4096, 128, 32, 0, False, "jnp"),        # rotary lanes not 64 a pair
    (4096, 160, 96, 64, False, "jnp"),       # rotary off a lane tile
])
def test_the_rule_chooses_by_shape_and_norm(t, head_dim, rotary_lanes, nope,
                                            normed, path):
    assert rotary_path(t, head_dim, rotary_lanes, nope=nope,
                       normed=normed) == path


def test_the_presets_say_which_rotary_runs():
    """What ``dopt.run`` prints beside the device: every layer kind of the
    laguna and joyai presets takes the kernels, keye's none (its rotary
    follows per-head norms)."""
    from dopt.presets import get_preset

    for name, want in [("laguna-localsgd2", "fused(3/3 kinds)"),
                       ("joyai-localsgd2", "fused(2/2 kinds)"),
                       ("keye-localsgd2", "jnp"),
                       ("laguna-localsgd2-toy", "jnp")]:
        model = get_preset(name).model
        worker = decoder.GatedMoEDecoder(model.decoder, vocab_rows=64)
        assert worker.rotary_path(model.input_shape[0]) == want, name
