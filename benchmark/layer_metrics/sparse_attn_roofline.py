"""Share of its roofline the sparse attention's body reaches, busiest
chip: the least time the chip could take for the attention a round
REQUIRES, over the device time under the program's ``dopt_attend`` scope
(``attend_ms``).

Required are the configuration's ``attention`` layers that carry a
``window``: a query attends ``min(t + 1, window)`` keys whichever the
indexer chose, so the band's count is exact.  The least time of a layer
and trained row is the larger of its operations over the chip's bf16
peak and its bytes over the HBM bandwidth, both as
``attn_kernel_roofline`` reckons them for a fused kernel
(``kernel_flops``: forward's two products and the backward's four;
``kernel_bytes``: q, k, v, o and their cotangents once each); for these
shapes operations bound it.  The program multiplies every block of
queries with every key up to the end of its run of blocks and masks,
holds float32 scores in HBM and computes the forward twice (a block's
``jax.checkpoint``): all of that is in the time and none of it in the
count, so the share cannot pass 100% and reads low until a fused kernel
takes a data-dependent mask.  Left out where the program has no such
scope or the configuration no such layer."""

from benchmark import flops
from benchmark.layer_metrics.attn_kernel_roofline import (kernel_bytes,
                                                          kernel_flops)
from benchmark.layer_metrics.local_ms import scoped_ms


def read(run):
    ms = scoped_ms(run, "dopt_attend")
    if not ms:
        return None
    layers = [layer for layer in run.config["layers"]
              if layer["op"] == "attention" and layer.get("window")]
    if not layers:
        return None
    peaks = flops.device_peaks(run.device_kind)
    kv_heads = run.config["num_key_value_heads"]
    least_s = sum(
        max(kernel_flops(layer) / peaks["flops_per_s_bf16"],
            kernel_bytes(layer, kv_heads) / peaks["hbm_bytes_per_s"])
        for layer in layers)
    return (100.0 * least_s * run.samples_per_round
            / (ms * 1e-3 * run.chips))
