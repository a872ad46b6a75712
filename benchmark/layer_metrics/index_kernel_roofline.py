"""Share of their roofline the lightning indexer's score kernels reach
(this repo's Pallas kernels ``dopt_attn_dopt_index_fwd`` and ``_bwd``,
found by name: a custom call may carry no jax name stack), busiest chip:
the least time the chip could take for the index scores a round
REQUIRES, forward and backward, over the device time of those kernels.

Required are the configuration's ``attention`` layers named
``*.indexer.scores`` (no ``window``: every query scores every key it
sees).  Of one trained row and layer the least time is the larger of

* its operations over the chip's bf16 peak, as ``attn_kernel_roofline``
  counts them (``kernel_flops``: the layer stands for the forward's ONE
  product of ``indexer_num_heads`` heads as the op's two of half as
  many, and the backward's two are each as large), and
* its bytes over the HBM bandwidth (``required_bytes``: the indexer's
  queries, key and head weights read once, the float32 triangle of
  scores written once and its cotangent read once);

for these shapes operations bound it.  What the program executes beyond
that is in the time and not in the count: the forward kernel again in a
block's ``jax.checkpoint`` recompute, the products formed again inside
the backward kernel, whole tiles of keys where the triangle cuts one,
and a contraction of 64 that fills half the MXU's depth; so the share
cannot pass 100% and tops out near 50%.  The indexer's projections, the
selection and the alignment term run outside the kernels, in
``index_ms`` and ``select_ms``, not here.

Left out where no such kernel ran: a program from before them, or shapes
the kernels do not take."""

from benchmark import flops
from benchmark.layer_metrics.attn_kernel_roofline import BYTES, kernel_flops
from benchmark.layer_metrics.local_ms import scoped_ms

KERNELS = "dopt_attn_dopt_index_"
FLOAT32 = 4


def required_bytes(layer: dict, heads: int, head_dim: int) -> int:
    """Bytes one trained row has to move between HBM and the kernels of
    one layer at the least: ``heads`` query heads and one key head of
    ``head_dim`` in the compute dtype and the float32 head weights read,
    the float32 scores of the causal triangle written, their cotangent
    read."""
    t = layer["positions"]
    inputs = BYTES * (heads + 1) * t * head_dim + FLOAT32 * t * heads
    return inputs + 2 * FLOAT32 * t * (t + 1) // 2


def read(run):
    ms = scoped_ms(run, KERNELS)
    if not ms:
        return None
    layers = [layer for layer in run.config.get("layers", ())
              if layer["op"] == "attention"
              and layer["name"].endswith(".indexer.scores")]
    indexer = run.config.get("sa_config")
    if not layers or not indexer:
        return None
    peaks = flops.device_peaks(run.device_kind)
    least_s = sum(
        max(kernel_flops(layer) / peaks["flops_per_s_bf16"],
            required_bytes(layer, indexer["indexer_num_heads"],
                           indexer["indexer_head_dim"])
            / peaks["hbm_bytes_per_s"])
        for layer in layers)
    return (100.0 * least_s * run.samples_per_round
            / (ms * 1e-3 * run.chips))
