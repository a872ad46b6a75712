"""Share of their roofline the held experts' grouped-matmul kernels reach
(this repo's Pallas kernels ``dopt_moe_experts_fwd``, ``_dx`` and
``_dw``, found by name: a custom call may carry no jax name stack),
busiest chip: the least time the chip could take for what the held
experts' three products REQUIRE a round, forward and backward, over the
device time of those kernels.

Required are the configuration's ``matmul`` layers that carry ``held``
(a sparse layer's ``experts.gate``, ``.up`` and ``.down``), a layer's
three together.  Of one trained row: the forward's product and the
backward's two (input and weight gradient) of each, at the multiply-adds
``ops/matmul.py`` counts (positions x ``active``: the EXPECTED routed
share under uniform routing, as ``program_mfu`` counts them).  Of one
worker's step (``local_bs`` rows of the traffic file): the held experts'
float32 leaves read once and their float32 gradients written once, which
the configuration's ``guarantees`` (float32 parameters and gradients)
make the least any program moves.  The least time of a layer is the
larger of the two; at the cells' 128 slots an expert the bytes bound
``laguna-xs2`` and the operations ``keye-vl2``.

What the program executes beyond that is in the time and not in the
count: tiles padded to 128 slots, a token's row copied alone (forward,
and twice more in the backward kernels), the weights read in the forward
AND the backward pass, a group's matrices fetched again for its every
tile.  The leaves' casts to the compute dtype and the slots' layout run
outside the kernels, in ``moe_ms`` and ``route_ms``, not here.

The numerator is the uniform-routing EXPECTATION (``program_mfu``'s
convention), the denominator the time of the routing that happened, so
the share moves with ``moe_held_slot_share``.  Where the bytes bound the
layer (``laguna-xs2``) the count is a true least and the share cannot
pass 100%.  Where the operations do (``keye-vl2``) it can read ABOVE the
kernels' true share when the router sends the held experts fewer slots
than uniform routing would: the keye preset's runs read a held share of
0.027-0.047 against the uniform 0.0625, so its count holds 1.3 to 2.3
times the operations any slot needed there (PERF.md, PR 34).

Left out where no such kernel ran: a program from before them, or shapes
the kernels do not take."""

from benchmark import flops
from benchmark.layer_metrics.local_ms import scoped_ms
from benchmark.ops import matmul

KERNELS = "dopt_moe_experts_"
FLOAT32 = 4


def required_flops(layer: dict) -> int:
    """Operations one TRAINED row requires of one held-expert product: the
    forward's and the backward's two, a multiply-add 2 operations."""
    return 3 * 2 * matmul.macs(layer)


def required_bytes(layer: dict) -> int:
    """Bytes one worker's STEP moves at the least for one held-expert
    product: its float32 leaf read, its float32 gradient written."""
    return 2 * FLOAT32 * matmul.params(layer)


def read(run):
    ms = scoped_ms(run, KERNELS)
    if not ms:
        return None
    layers = [layer for layer in run.config.get("layers", ())
              if layer["op"] == "matmul" and layer.get("held")]
    if not layers:
        return None
    peaks = flops.device_peaks(run.device_kind)
    steps = run.samples_per_round / run.traffic["gossip"]["local_bs"]
    by_layer = {}
    for layer in layers:        # gate, up and down of one expert layer
        by_layer.setdefault(layer["name"].split(".")[0], []).append(layer)
    least_s = sum(
        max(sum(map(required_flops, three)) * run.samples_per_round
            / peaks["flops_per_s_bf16"],
            sum(map(required_bytes, three)) * steps
            / peaks["hbm_bytes_per_s"])
        for three in by_layer.values())
    return 100.0 * least_s / (ms * 1e-3 * run.chips)
