"""Share of its roofline the fused attention kernels reach (jax's Pallas
TPU splash attention: the device ops named ``splash_mqa_fwd``, ``_dq``
and ``_dkv``; the compiler leaves them without a jax name stack),
busiest chip: the least time the chip could take for the attention a
round REQUIRES, over the device time of those kernels.

The least time is the larger of operations over the chip's bf16 peak
and bytes over its HBM bandwidth, summed over the configuration's
``attention`` layers and the rows a round trains (``kernel_flops``,
``kernel_bytes`` below; for these shapes operations bound it).  What the
program executes beyond that (the forward kernel again under a layer's
``jax.checkpoint``, the scores recomputed inside the backward kernels,
blocks a mask only partly empties) is in the time and not in the count,
so the share cannot pass 100%.

Left out where no such kernel ran: a program from before them, or
shapes the kernel does not take."""

from benchmark import flops
from benchmark.layer_metrics.local_ms import scoped_ms
from benchmark.ops import attention

KERNELS = "splash_mqa_"
BYTES = 2   # bfloat16 in and out of the kernels


def kernel_flops(layer: dict) -> int:
    """Operations one TRAINED row requires of the attention kernels of
    one layer: the forward's two products (``ops/attention.py``'s
    ``macs``: scores and values over the causal triangle or band) and
    the backward's four (dV, dP, dQ, dK), each as large as one of the
    forward's; a multiply-add is 2 operations."""
    return 3 * 2 * attention.macs(layer)


def kernel_bytes(layer: dict, kv_heads: int) -> int:
    """Bytes one trained row has to move between HBM and the kernels of
    one layer at the least: forward reads q, k, v and writes o; backward
    reads q, k, v, o, do and writes dq, dk, dv.  q-shaped arrays are
    ``positions x heads x head_dim``, k-shaped ones have ``kv_heads``."""
    q = layer["positions"] * layer["heads"] * layer["head_dim"]
    k = layer["positions"] * kv_heads * layer["head_dim"]
    return BYTES * (6 * q + 6 * k)


def read(run):
    ms = scoped_ms(run, KERNELS)
    if not ms:
        return None
    peaks = flops.device_peaks(run.device_kind)
    kv_heads = run.config["num_key_value_heads"]
    least_s = sum(
        max(kernel_flops(layer) / peaks["flops_per_s_bf16"],
            kernel_bytes(layer, kv_heads) / peaks["hbm_bytes_per_s"])
        for layer in run.config["layers"] if layer["op"] == "attention")
    return (100.0 * least_s * run.samples_per_round
            / (ms * 1e-3 * run.chips))
