"""Milliseconds a round keeps the device busy: the union of device-op
intervals in the traced steady window, on the busiest chip, per round."""

from benchmark import trace_reduce as tr


def read(run):
    if run.reduced is None:
        return None
    busy = max(tr.busy_ns(ops) for ops in run.reduced.devices.values())
    return busy * 1e-6 / run.rounds
