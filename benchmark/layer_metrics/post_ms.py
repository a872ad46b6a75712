"""Host milliseconds a round spends after the device is done: the one
fetch of the packed metrics (``round_fetch``) and the unpack, ledger,
history rows and telemetry (``round_record``), the program's timers
inside the window."""

SPANS = ("round_fetch", "round_record")


def read(run):
    if not any(s in run.host_span_s for s in SPANS):
        return None
    return 1e3 * sum(run.host_span_s.get(s, 0.0) for s in SPANS) / run.rounds
