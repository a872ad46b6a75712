"""Device milliseconds a round spends in the local phase (every worker's
SGD steps with their batch gathers and, under a holdout, the per-epoch
local eval): ops under the program's ``dopt_local`` scope, busiest chip.

Every cell trains, so a program that has the scope and a trace that shows
none of it is an error, not a zero."""

from benchmark import trace_reduce as tr

SCOPE = "dopt_local"


def scoped_ms(run, scope):
    """ms a round under ``scope`` on the busiest chip; ``None`` where
    there is no device plane (the rehearsal) or the program is from
    before the scopes (it has no ``round_dispatch`` timer either: both
    came with PROGRAM_METADATA_VERSION 1)."""
    if run.reduced is None or "round_dispatch" not in run.host_span_s:
        return None
    ns = max(tr.scope_ns(ops, scope) for ops in run.reduced.devices.values())
    return ns * 1e-6 / run.rounds


def read(run):
    ms = scoped_ms(run, SCOPE)
    if ms == 0.0:
        raise ValueError(
            f"no device op of the traced round carries {SCOPE!r}: the "
            "executable came from a compile cache filled before the scope "
            "was added. Bump PROGRAM_METADATA_VERSION in "
            "dopt/utils/compile_cache.py whenever a scope is added or "
            "renamed (the cache key ignores the op_name metadata)")
    return ms
