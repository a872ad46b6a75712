"""Milliseconds a round spends in collective ops while nothing else runs
on that chip (what the interconnect costs the round), worst chip."""

from benchmark import trace_reduce as tr


def read(run):
    if run.reduced is None:
        return None
    red = run.reduced
    exposed = max(
        tr.collective_ns(ops, red.async_collectives.get(name, ()))[1]
        for name, ops in red.devices.items())
    return exposed * 1e-6 / run.rounds
