"""Device milliseconds a round spends in convolution ops (self time, by
the op's own class), busiest chip."""

from benchmark import trace_reduce as tr


def read(run):
    if run.reduced is None:
        return None
    conv = max(tr.phase_ns(ops)["conv"]
                    for ops in run.reduced.devices.values())
    return conv * 1e-6 / run.rounds
