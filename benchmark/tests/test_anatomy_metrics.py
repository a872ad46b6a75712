"""The six readers of the round's anatomy (``dispatch_ms``, ``wait_ms``,
``post_ms``, ``local_ms``, ``batch_ms``, ``eval_ms``) on a hand-made
``LayerInput``, and the gap attribution when the program's spans reach
the trace twice (natively and through the harness's tracer shim)."""

import importlib

import pytest

from benchmark import trace_reduce as tr
from benchmark.context import LayerInput
from benchmark.trace_reduce import Op, Reduced

HOST = {"host_batch_plan": 0.012, "round_step": 3.0, "round_dispatch": 0.09,
        "round_wait": 2.91, "round_fetch": 0.003, "round_record": 0.006}
# The parent of the PR that brought the spans: two timers, two scopes.
OLD_HOST = {"host_batch_plan": 0.012, "round_step": 3.0}


def op(start, end, name, stack=""):
    return Op(float(start), float(end), name, stack)


def chip(scale=1.0):
    """Three rounds on one chip, ns: a local while with a batch gather and
    an update in it, an eval while, a mix."""
    j = "jit(compact_round_fn)/"
    ops = []
    for r in range(3):
        t = r * 1000e6
        ops += [
            op(t, t + 600e6 * scale, "while.1", j + "dopt_local/while"),
            op(t + 10e6, t + 40e6 * scale, "gather.3",
               j + "dopt_local/while/body/closed_call/dopt_batch/gather"),
            op(t + 50e6, t + 90e6, "fusion.7",
               j + "dopt_local/while/body/closed_call/dopt_update/sub"),
            op(t + 600e6, t + 900e6, "while.2", j + "dopt_eval/while"),
            op(t + 610e6, t + 620e6, "gather.9",
               j + "dopt_eval/dopt_batch/gather"),
            op(t + 900e6, t + 905e6, "fusion.11", j + "dopt_mix/reduce_sum"),
            op(t + 905e6, t + 930e6, "fusion.12", j + "concatenate"),
        ]
    return ops


def layer_input(reduced, host):
    return LayerInput(
        reduced=reduced, rounds=3, chips=2, device_kind="TPU v5 lite",
        config={}, traffic={}, samples_per_round=1, host_span_s=host,
        compile_s=0.0, round_hlo="", peak_bytes=[0])


def reduced(devices):
    return Reduced(window=(0.0, 3000e6), devices=devices, host_spans=[])


def read(metric, run):
    return importlib.import_module(
        f"benchmark.layer_metrics.{metric}").read(run)


TRACED = layer_input(reduced({"/device:TPU:0": chip(),
                              "/device:TPU:1": chip(0.5)}), HOST)


@pytest.mark.parametrize("metric, value", [
    ("dispatch_ms", 30.0), ("wait_ms", 970.0), ("post_ms", 3.0),
    ("local_ms", 600.0), ("batch_ms", 40.0), ("eval_ms", 300.0)])
def test_value(metric, value):
    # the device readers take the busiest chip, all of them a round
    assert read(metric, TRACED) == pytest.approx(value)


@pytest.mark.parametrize("metric", ["local_ms", "batch_ms", "eval_ms"])
def test_device_readers_read_nothing_in_the_rehearsal(metric):
    assert read(metric, layer_input(None, HOST)) is None


@pytest.mark.parametrize("metric", ["dispatch_ms", "wait_ms", "post_ms",
                                    "local_ms", "batch_ms", "eval_ms"])
def test_a_program_from_before_the_spans_reports_nothing(metric):
    """The driver lays these readers over the parent's checkout: no
    timer, no scope, no metric, and no error."""
    old = [op(o.start, o.end, o.name, "jit(compact_round_fn)/while")
           for o in chip()]
    run = layer_input(reduced({"/device:TPU:0": old}), OLD_HOST)
    assert read(metric, run) is None


def test_local_ms_raises_on_a_stale_executable():
    """The program has the scopes (it times ``round_dispatch``) and the
    trace shows none: the executable came from an old compile cache."""
    stale = [op(o.start, o.end, o.name, "jit(compact_round_fn)/while")
             for o in chip()]
    run = layer_input(reduced({"/device:TPU:0": stale}), HOST)
    with pytest.raises(ValueError, match="PROGRAM_METADATA_VERSION"):
        read("local_ms", run)
    # a cell need not gather or evaluate on the device: those read zero
    assert read("batch_ms", run) == 0.0
    assert read("eval_ms", run) == 0.0


def test_gap_attribution_is_the_same_when_every_span_appears_twice():
    """``PhaseTimers`` writes each span to the profiler itself and the
    harness's ``TraceSpans`` shim writes it again, nested inside: the
    innermost (shortest) span still wins, under the same name."""
    ops = [op(100, 400, "fusion.1"), op(450, 900, "fusion.2")]
    window = (0.0, 1000.0)
    once = [("bench.window", 0.0, 1000.0), ("bench.run_call", 0.0, 1000.0),
            ("host_batch_plan", 10.0, 95.0), ("round_step", 96.0, 990.0)]
    twice = once + [(name, s + 1.0, e - 1.0) for name, s, e in once[2:]]
    assert (tr.gaps_by_host_span(ops, window, twice)
            == tr.gaps_by_host_span(ops, window, once)
            == [["round_step", pytest.approx(150e-9)],
                ["host_batch_plan", pytest.approx(100e-9)]])
