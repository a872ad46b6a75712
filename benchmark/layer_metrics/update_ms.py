"""Device milliseconds a round spends under the program's
``dopt_update`` scope (the momentum-SGD update), busiest chip."""

from benchmark import trace_reduce as tr


def read(run):
    if run.reduced is None:
        return None
    upd = max(tr.scope_ns(ops, "dopt_update")
                   for ops in run.reduced.devices.values())
    return upd * 1e-6 / run.rounds
