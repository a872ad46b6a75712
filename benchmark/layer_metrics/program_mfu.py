"""Model FLOP/s utilisation of the round program on the DEVICE basis:
operations the configured job requires a round (3 forward passes' worth
for each trained sample, 1 for each evaluated one, from the
configuration's shapes) over busy device time x chips x the chip's bf16
peak.  Idle time is not in it (``device_idle`` has that), and it is not
a kernel's roofline share."""

from benchmark import flops
from benchmark.layer_metrics import device_ms_per_round


def read(run):
    busy_ms = device_ms_per_round.read(run)
    if busy_ms is None:
        return None
    need = flops.round_flops(
        run.config["layers"], train_samples=run.samples_per_round,
        eval_forwards=run.traffic["eval_forwards_per_round"])
    peak = flops.device_peaks(run.device_kind)["flops_per_s_bf16"]
    return 100.0 * need / (busy_ms * 1e-3 * run.chips * peak)
