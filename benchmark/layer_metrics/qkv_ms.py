"""Device milliseconds a round spends in the decoder attention's input
projections, forward and backward: q, k and v out of the normed layer
input, head-major, and in a layer with a per-head output gate the gate's
projection.  Ops under the program's ``dopt_qkv`` scope (inside
``dopt_attn``; a recompute under ``rematted_computation`` counts here and
in ``remat_ms`` both), busiest chip.  Left out where the program has no
such scope."""

from benchmark.layer_metrics.local_ms import scoped_ms


def read(run):
    return scoped_ms(run, "dopt_qkv") or None
