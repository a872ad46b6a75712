"""``remat_ms`` on the hand-made trace of ``test_anatomy_metrics``: it
reads jax's ``rematted_computation`` name wherever it stands in an op's
name stack (a layer's recompute, the head block's inside its while),
0.0 on a program without a checkpoint, nothing in the rehearsal."""

import pytest

from benchmark.tests.test_anatomy_metrics import (HOST, OLD_HOST, chip,
                                                  layer_input, op, read,
                                                  reduced)


def remat_chip(scale=1.0):
    """``chip()`` with a checkpointed decoder layer's backward inside each
    round's local while, as the compiled step names it: backward products
    (``checkpoint/dopt_*``: not a recompute) and the recompute itself."""
    j = ("jit(round_fn)/dopt_local/while/body/closed_call/"
         "transpose(jvp(vmap(jvp(vmap()))))/checkpoint/")
    ops = chip(scale)
    for r in range(3):
        t = r * 1000e6
        ops += [
            op(t + 100e6, t + 140e6, "fusion.20",
               j + "dopt_attn/td,dne->nte/dot_general"),
            op(t + 140e6, t + 170e6 * scale, "fusion.21",
               j + "rematted_computation/dopt_attn/td,dne->nte/dot_general"),
            op(t + 170e6, t + 180e6, "fusion.22",
               j + "rematted_computation/dopt_moe/dopt_route/top_k"),
            op(t + 300e6, t + 305e6, "fusion.23",
               "jit(round_fn)/dopt_local/while/body/closed_call/"
               "transpose(jvp(dopt_head))/while/body/closed_call/"
               "checkpoint/rematted_computation/dot_general"),
        ]
    return ops


def test_reads_the_recompute_on_the_busiest_chip():
    run = layer_input(reduced({"/device:TPU:0": remat_chip(),
                               "/device:TPU:1": remat_chip(0.9)}), HOST)
    assert read("remat_ms", run) == pytest.approx(30.0 + 10.0 + 5.0)
    # nested in the local phase, which it leaves as it was
    assert read("local_ms", run) == pytest.approx(600.0)


def test_zero_on_a_program_without_a_checkpoint():
    run = layer_input(reduced({"/device:TPU:0": chip()}), HOST)
    assert read("remat_ms", run) == 0.0


def test_nothing_in_the_rehearsal_or_before_the_spans():
    assert read("remat_ms", layer_input(None, HOST)) is None
    assert read("remat_ms", layer_input(
        reduced({"/device:TPU:0": chip()}), OLD_HOST)) is None
