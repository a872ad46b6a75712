"""The readers of the decoder layer's anatomy (``qkv_ms``, ``rope_ms``,
``oproj_ms``, ``splash_glue_ms``, ``mlp_ms``, ``shared_ms`` and the two
remainders ``attn_rest_ms``, ``local_rest_ms``) on the hand-made trace of
``test_anatomy_metrics``: their values, the two identities the remainders
are cut to, and nothing read on a program without the scopes or on a
layer that is not there."""

import dataclasses

import pytest

from benchmark import trace_reduce as tr
from benchmark.layer_metrics import attn_rest_ms, local_rest_ms
from benchmark.tests.test_anatomy_metrics import (HOST, OLD_HOST, chip,
                                                  layer_input, op, read,
                                                  reduced)

METRICS = ("qkv_ms", "rope_ms", "oproj_ms", "splash_glue_ms", "mlp_ms",
           "shared_ms", "attn_rest_ms", "local_rest_ms")
L = "jit(round_fn)/dopt_local/while/body/closed_call/"
A = L + "checkpoint/dopt_attn/"
B = L + "transpose(jvp(checkpoint))/dopt_attn/"


def decoder_chip(scale=1.0, dense=True, shared=True):
    """``chip()`` with one decoder layer's parts inside each round's local
    while (0 .. 600 ms of a round: batch 10 .. 40, update 50 .. 90), ms:
    the layer-input norm 100 .. 105 and the residual add 200 .. 203 under
    ``dopt_attn`` alone, the embedding 300 .. 302 under nothing; the
    splash kernels go by name, forward with no stack and backward with
    the scope's."""
    ops = chip()
    for r in range(3):
        t = r * 1000e6

        def at(s, e, name, stack):
            ops.append(op(t + s * 1e6, t + e * 1e6, name, stack))

        at(100, 105, "fusion.20", A + "mul")
        at(105, 105 + 20 * scale, "fusion.21",
           A + "dopt_qkv/td,dne->nte/dot_general")
        at(125, 140, "fusion.22", A + "dopt_rope/mul")
        at(140, 145, "copy.3", A + "dopt_splash/copy")
        at(145, 175, "splash_mqa_fwd.1", "")
        at(175, 180, "copy.4", A + "dopt_splash/transpose")
        at(180, 200, "fusion.24", A + "dopt_oproj/nte,ned->td/dot_general")
        at(200, 203, "add.5", A + "add")
        if dense:
            at(203, 210, "fusion.25", L + "checkpoint/dopt_mlp/dot_general")
        at(210, 212, "add.6", L + "checkpoint/add")
        at(212, 230, "fusion.26", L + "checkpoint/dopt_moe/dopt_route/dot")
        if shared:
            at(230, 240, "fusion.27",
               L + "checkpoint/dopt_moe/dopt_shared/dot_general")
        at(240, 250, "dopt_moe_experts_fwd.2",
           L + "checkpoint/dopt_moe/dopt_moe_experts_fwd/pallas_call")
        at(250, 260, "splash_mqa_dkv.3", B + "dopt_splash/pallas_call")
        at(260, 270, "fusion.28", B + "dopt_qkv/transpose")
        at(270, 276, "fusion.29",
           B + "rematted_computation/dopt_rope/mul")
        at(280, 300, "fusion.30", L + "dopt_head/dot_general")
        at(300, 302, "gather.31", L + "take")
    return ops


RUN = layer_input(reduced({"/device:TPU:0": decoder_chip(),
                           "/device:TPU:1": decoder_chip(0.5)}), HOST)


@pytest.mark.parametrize("metric, value", [
    ("qkv_ms", 30.0),            # 105..125 forward, 260..270 backward
    ("rope_ms", 21.0),           # 125..140, 270..276 recomputed
    ("oproj_ms", 20.0),
    ("splash_glue_ms", 10.0),    # 140..145 and 175..180, no kernel
    ("mlp_ms", 7.0),
    ("shared_ms", 10.0),
    ("attn_rest_ms", 8.0),       # the norm 5 and the residual add 3
    # the local while's 600 less batch 30, update 40, attention 129,
    # the dense MLP 7, the experts 38, the head 20
    ("local_rest_ms", 336.0)])
def test_value(metric, value):
    assert read(metric, RUN) == pytest.approx(value)
    assert read("attn_ms", RUN) == pytest.approx(129.0)
    assert read("remat_ms", RUN) == pytest.approx(6.0)


def test_the_remainders_complete_their_parents_exactly():
    """``attn_ms`` = the union of its children + ``attn_rest_ms`` and
    ``local_ms`` = the union of its children under it + ``local_rest_ms``,
    on the chip each parent reads (the busier one), to the nanosecond."""
    ops = RUN.reduced.devices["/device:TPU:0"]

    def union_ms(marks, inside=None):
        merged = tr.merge((o.start, o.end) for o in ops
                          if any(m in o.text for m in marks))
        if inside is not None:
            merged = tr.subtract(merged, tr.subtract([(0.0, 3000e6)],
                                                     tr.merge(inside)))
        return tr.length(merged) * 1e-6 / RUN.rounds

    assert read("attn_ms", RUN) == (union_ms(attn_rest_ms.CHILDREN)
                                    + read("attn_rest_ms", RUN))
    local = [(o.start, o.end) for o in ops if "dopt_local" in o.text]
    # (the round's own dopt_eval, after the local while, is not under it)
    assert read("local_ms", RUN) == pytest.approx(
        union_ms(local_rest_ms.CHILDREN, local)
        + read("local_rest_ms", RUN), abs=1e-9)


def test_a_remainder_is_what_the_parent_covers_and_no_child_does():
    """The reader's subtraction is the difference of the two unions, also
    where a child lies outside its parent (the round's ``dopt_eval``)."""
    ops = RUN.reduced.devices["/device:TPU:0"]
    for parent, children, metric in [
            (attn_rest_ms.PARENT, attn_rest_ms.CHILDREN, "attn_rest_ms"),
            (("dopt_local",), local_rest_ms.CHILDREN, "local_rest_ms")]:
        whole = tr.merge((o.start, o.end) for o in ops
                         if any(m in o.text for m in parent + children))
        kids = tr.merge((o.start, o.end) for o in ops
                        if any(m in o.text for m in children))
        assert read(metric, RUN) == pytest.approx(
            (tr.length(whole) - tr.length(kids)) * 1e-6 / RUN.rounds)


@pytest.mark.parametrize("metric", METRICS)
def test_nothing_to_read_without_the_scopes(metric):
    """``None``, so that the line leaves the metric out: in the rehearsal,
    on a program from before the spans, and on one from before these
    scopes, whose layer carries ``dopt_attn`` and ``dopt_moe`` but none
    of the six parts' scopes."""
    assert read(metric, layer_input(None, HOST)) is None
    assert read(metric, layer_input(
        reduced({"/device:TPU:0": decoder_chip()}), OLD_HOST)) is None
    new = ("dopt_qkv/", "dopt_rope/", "dopt_oproj/", "dopt_splash/",
           "dopt_mlp/", "dopt_shared/")
    unscoped = []
    for o in decoder_chip():
        stack = o.stack
        for scope in new:
            stack = stack.replace(scope, "")
        unscoped.append(dataclasses.replace(o, stack=stack))
    assert read(metric, layer_input(
        reduced({"/device:TPU:0": unscoped}), HOST)) is None
    assert read("attn_ms", layer_input(
        reduced({"/device:TPU:0": unscoped}), HOST)) == pytest.approx(129.0)


@pytest.mark.parametrize("metric, dense, shared", [
    ("mlp_ms", False, True), ("shared_ms", True, False)])
def test_nothing_to_read_for_a_part_the_layer_lacks(metric, dense, shared):
    """keye's layers have no dense MLP and no shared expert; the rest of
    the anatomy is read there all the same."""
    run = layer_input(reduced({"/device:TPU:0": decoder_chip(
        dense=dense, shared=shared)}), HOST)
    assert read(metric, run) is None
    assert read("qkv_ms", run) == pytest.approx(30.0)
    assert read("local_rest_ms", run) is not None


def test_splash_glue_leaves_out_a_kernel_that_carries_the_scope():
    """The backward kernel carries ``…/dopt_splash/pallas_call``: its 10
    ms are the kernel's, not the glue's; on the ``jax.numpy`` blocks
    (no kernel, no scope) the metric is left out."""
    ops = RUN.reduced.devices["/device:TPU:0"]
    assert tr.scope_ns(ops, "dopt_splash") * 1e-6 / RUN.rounds == 20.0
    assert read("splash_glue_ms", RUN) == pytest.approx(10.0)
    blocked = [o for o in decoder_chip()
               if "splash" not in o.text]
    assert read("splash_glue_ms", layer_input(
        reduced({"/device:TPU:0": blocked}), HOST)) is None
