"""``pool_ms`` on the hand-made trace of ``test_anatomy_metrics``: it
reads the ``dopt_pool`` scope, 0.0 on a program without it (the parent
of the PR that brought it), nothing in the rehearsal."""

import pytest

from benchmark.tests.test_anatomy_metrics import (HOST, OLD_HOST, chip,
                                                  layer_input, op, read,
                                                  reduced)


def pooled_chip(scale=1.0):
    """``chip()`` with a pool forward and the backward's two broadcasts
    inside each round's local while, as the compiled step names them."""
    j = "jit(compact_round_fn)/dopt_local/while/body/closed_call/"
    ops = chip(scale)
    for r in range(3):
        t = r * 1000e6
        ops += [
            op(t + 100e6, t + 130e6 * scale, "add_reduce_fusion",
               j + "jvp(dopt_pool)/reduce"),
            op(t + 200e6, t + 210e6, "broadcast.59",
               j + "transpose(jvp(dopt_pool))/broadcast_in_dim"),
            op(t + 210e6, t + 220e6, "broadcast.53",
               j + "transpose(jvp(dopt_pool))/eq"),
        ]
    return ops


def test_reads_the_scope_on_the_busiest_chip():
    run = layer_input(reduced({"/device:TPU:0": pooled_chip(),
                               "/device:TPU:1": pooled_chip(0.9)}), HOST)
    assert read("pool_ms", run) == pytest.approx(30.0 + 10.0 + 10.0)
    # the scope is nested in the local phase and leaves it as it was
    assert read("local_ms", run) == pytest.approx(600.0)


def test_zero_on_a_program_without_the_scope():
    run = layer_input(reduced({"/device:TPU:0": chip()}), HOST)
    assert read("pool_ms", run) == 0.0


def test_nothing_in_the_rehearsal_or_before_the_spans():
    assert read("pool_ms", layer_input(None, HOST)) is None
    assert read("pool_ms", layer_input(
        reduced({"/device:TPU:0": chip()}), OLD_HOST)) is None
