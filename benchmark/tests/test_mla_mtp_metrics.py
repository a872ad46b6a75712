"""The readers of the latent attention's down-projections (``latent_ms``,
scope ``dopt_latent`` inside ``dopt_qkv``) and of the multi-token-
prediction module (``mtp_ms``, scope ``dopt_mtp``) on a hand-made trace
of two chips: their values on the busier chip, what they share with the
readers they nest in, and nothing read on a program without the scopes."""

import dataclasses

import pytest

from benchmark.tests.test_anatomy_metrics import (HOST, OLD_HOST, chip,
                                                  layer_input, op, read,
                                                  reduced)

L = "jit(round_fn)/dopt_local/while/body/closed_call/"
A = L + "checkpoint/dopt_attn/dopt_qkv/"
B = L + "transpose(jvp(checkpoint))/dopt_attn/dopt_qkv/"
M = L + "dopt_mtp/"


def mla_chip(scale=1.0):
    """``chip()`` with, inside each round's local while (ms): the latent
    down-projections 100 .. 100 + 10 * scale forward and 200 .. 206 in
    the backward, the up-projection 120 .. 130 under ``dopt_qkv`` alone;
    the module's projection 300 .. 304, its block's attention 304 .. 314
    (down-projections 304 .. 306 among it) and experts 314 .. 320, its
    head pass 320 .. 330, its backward 400 .. 410."""
    ops = chip()
    for r in range(3):
        t = r * 1000e6

        def at(s, e, name, stack):
            ops.append(op(t + s * 1e6, t + e * 1e6, name, stack))

        at(100, 100 + 10 * scale, "fusion.40",
           A + "dopt_latent/td,dr->tr/dot_general")
        at(120, 130, "fusion.41", A + "tr,rne->nte/dot_general")
        at(200, 206, "fusion.42", B + "dopt_latent/transpose")
        at(300, 304, "fusion.43", M + "dot_general")
        at(304, 306, "fusion.44", M + "checkpoint/dopt_attn/dopt_qkv/"
           "dopt_latent/dot_general")
        at(306, 314, "fusion.45", M + "checkpoint/dopt_attn/mul")
        at(314, 320, "fusion.46", M + "checkpoint/dopt_moe/dot_general")
        at(320, 330, "fusion.47", M + "dopt_head/dot_general")
        at(400, 410, "fusion.48",
           L + "transpose(jvp(remat))/dopt_mtp/dopt_head/transpose")
    return ops


RUN = layer_input(reduced({"/device:TPU:0": mla_chip(),
                           "/device:TPU:1": mla_chip(0.5)}), HOST)


@pytest.mark.parametrize("metric, value", [
    ("latent_ms", 18.0),      # 100..110, 200..206, 304..306
    ("mtp_ms", 40.0),         # 300..330 and 400..410
])
def test_value(metric, value):
    assert read(metric, RUN) == pytest.approx(value)


def test_the_scopes_nest_in_their_readers_parents():
    """``dopt_latent`` is part of ``qkv_ms``; the module's head pass is
    part of ``head_ms`` and its block's attention and experts of
    ``attn_ms`` and ``moe_ms``."""
    assert read("qkv_ms", RUN) == pytest.approx(10 + 10 + 6 + 2)
    assert read("head_ms", RUN) == pytest.approx(20.0)
    assert read("attn_ms", RUN) == pytest.approx(10 + 10 + 6 + 10)
    assert read("moe_ms", RUN) == pytest.approx(6.0)


@pytest.mark.parametrize("metric", ["latent_ms", "mtp_ms"])
def test_nothing_to_read_without_the_scopes(metric):
    """``None``, so that the line leaves the metric out: in the rehearsal,
    on a program from before the spans, and on a model without latent
    attention or a module (the other decoders, the parent's program)."""
    assert read(metric, layer_input(None, HOST)) is None
    assert read(metric, layer_input(
        reduced({"/device:TPU:0": mla_chip()}), OLD_HOST)) is None
    bare = [dataclasses.replace(
        o, stack=o.stack.replace("dopt_latent/", "").replace("dopt_mtp/", ""))
        for o in mla_chip()]
    assert read(metric, layer_input(
        reduced({"/device:TPU:0": bare}), HOST)) is None
    assert read("qkv_ms", layer_input(
        reduced({"/device:TPU:0": bare}), HOST)) == pytest.approx(28.0)
