"""Share of the traced steady window in which no op ran on the device,
on the chip where that share is largest."""

from benchmark import trace_reduce as tr


def read(run):
    if run.reduced is None:
        return None
    window = run.reduced.window[1] - run.reduced.window[0]
    idle = max(1.0 - tr.busy_ns(ops) / window
                    for ops in run.reduced.devices.values())
    return 100.0 * idle
