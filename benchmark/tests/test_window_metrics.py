"""The window's arithmetic: one stalled call may not be the run's number."""

import pytest

from benchmark import run

# Per-call milliseconds of two untraced runs of the FedAvg cell on the v5e
# (chip runs, PR 22): seed 8 ran clean, seed 7 stalled once for 1.1 s.
CLEAN = [1734.0, 1734.7, 1733.9, 1734.7, 1733.8, 1733.9, 1733.7, 1733.4,
         1734.0, 1733.6, 1734.2, 1734.3]
STALLED = [1733.8, 1733.9, 1733.5, 1733.5, 2831.4, 1733.7, 1733.3, 1733.6,
           1733.6, 1733.6, 1733.8]


def window(call_ms):
    return run.Window(
        setup_s=1.0, warmup_s=[], call_s=[1e-3 * c for c in call_ms],
        rounds_per_call=1, samples_per_round=150_000, losses={},
        host_span_s={}, compile_s=0.0, programs=0, cache_hits=0,
        compiles_in_window=0, peak_bytes=[0], round_hlo="",
        round_memory={}, trace_dir=None)


def test_one_stalled_call_moves_neither_rate_nor_median():
    clean = run.end_to_end(window(CLEAN), None)
    stalled = run.end_to_end(window(STALLED), None)
    for name in ("train_samples_per_s", "round_ms_p50"):
        assert stalled[name] == pytest.approx(clean[name], rel=5e-4)
    # ... which the plain mean over the window did not survive (5.4%).
    plain = 150_000 * len(STALLED) / (1e-3 * sum(STALLED))
    assert plain < 0.95 * clean["train_samples_per_s"]


def test_rate_counts_every_round_of_a_call():
    win = window([2000.0] * 10)
    win.rounds_per_call = 2
    assert run.end_to_end(win, None)["train_samples_per_s"] == 150_000


def test_a_slowdown_of_more_than_a_fifth_of_the_calls_shows():
    slowed = [1734.0] * 7 + [2600.0] * 4
    rate = run.end_to_end(window(slowed), None)["train_samples_per_s"]
    assert rate < 0.93 * 150_000 / 1.734


@pytest.mark.parametrize("n", [1, 3, 4])
def test_a_short_window_is_a_plain_mean(n):
    calls = [1.0, 2.0, 3.0, 6.0][:n]
    assert run.steady_call_s(calls) == pytest.approx(sum(calls) / n)
