"""``conv1_ms`` on the hand-made trace of ``test_anatomy_metrics``: it
reads the ``dopt_conv1`` scope wherever it is nested (training's forward
and backward, the evaluation), 0.0 on a program without it (the parent
of the PR that brought it), nothing in the rehearsal."""

import pytest

from benchmark.tests.test_anatomy_metrics import (HOST, OLD_HOST, chip,
                                                  layer_input, op, read,
                                                  reduced)


def conv1_chip(scale=1.0):
    """``chip()`` with the packed first convolution in each round's local
    while (forward, weight gradient) and in its evaluation, as the
    compiled round names them."""
    j = "jit(compact_round_fn)/"
    step = j + "dopt_local/while/body/closed_call/"
    ops = chip(scale)
    for r in range(3):
        t = r * 1000e6
        ops += [
            op(t + 100e6, t + 140e6 * scale, "convolution_add_fusion",
               step + "jvp(dopt_conv1)/conv_general_dilated"),
            op(t + 200e6, t + 220e6, "convolution.7",
               step + "transpose(jvp(dopt_conv1))/conv_general_dilated"),
            op(t + 700e6, t + 730e6, "convolution_add_fusion.1",
               j + "dopt_eval/while/body/dopt_conv1/add"),
        ]
    return ops


def test_reads_the_scope_on_the_busiest_chip():
    run = layer_input(reduced({"/device:TPU:0": conv1_chip(),
                               "/device:TPU:1": conv1_chip(0.9)}), HOST)
    assert read("conv1_ms", run) == pytest.approx(40.0 + 20.0 + 30.0)
    # nested in the local phase and in the evaluation, which stay as they were
    assert read("local_ms", run) == pytest.approx(600.0)
    assert read("eval_ms", run) == pytest.approx(300.0)


def test_zero_on_a_program_without_the_scope():
    run = layer_input(reduced({"/device:TPU:0": chip()}), HOST)
    assert read("conv1_ms", run) == 0.0


def test_nothing_in_the_rehearsal_or_before_the_spans():
    assert read("conv1_ms", layer_input(None, HOST)) is None
    assert read("conv1_ms", layer_input(
        reduced({"/device:TPU:0": chip()}), OLD_HOST)) is None
