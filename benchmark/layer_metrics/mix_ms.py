"""Device milliseconds a round spends mixing or aggregating: ops under
the program's ``dopt_mix`` scope together with every collective op,
busiest chip."""

from benchmark import trace_reduce as tr


def _mix(ops, async_ops):
    return tr.length(tr.merge(
        [(o.start, o.end) for o in ops
         if "dopt_mix" in o.text or tr.is_collective(o)]
        + [(o.start, o.end) for o in async_ops]))


def read(run):
    if run.reduced is None:
        return None
    red = run.reduced
    return max(_mix(ops, red.async_collectives.get(name, ()))
                    for name, ops in red.devices.items()) * 1e-6 / run.rounds
