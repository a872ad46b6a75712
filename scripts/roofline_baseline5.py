"""Per-layer/roofline analysis for baseline5 (32-worker ResNet-18 gossip
— the BASELINE.json north-star config).

Answers VERDICT r3 weak #4: is the measured MFU a CIFAR-spatial-conv
ceiling or recoverable?  Three numbers, all measured on the chip:

1. **Measured device time per round** — from the committed XLA trace
   (``results/trace_baseline5.json``, written by trace_roofline.py),
   which is immune to host wall-clock noise.
2. **Fleet-independence bound** — the same per-sample training step
   with ONE weight set at the same total batch (W=1, B=W·local_bs).
   No stacked-fleet engine can beat this: it removes the per-worker
   weights entirely, so the gap between it and (1) is the true cost of
   carrying 32 independent models (grouped-conv inefficiency at
   feature_group_count=32, per-worker GroupNorm, stacked head).
3. **MFU on the device-time basis** — samples/s·FLOPs/sample against
   the chip's bf16 peak, with FLOPs from XLA's own cost analysis.

Usage: python scripts/roofline_baseline5.py [--out results/roofline_baseline5.json]
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def measure_w1_bound(batch: int, steps: int = 12) -> float:
    """Marginal per-step seconds for a single-weight-set ResNet-18
    training step at the fleet's total batch (the bound no stacked
    engine can beat)."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    from dopt.models import build_model
    from dopt.models.losses import cross_entropy
    from dopt.optim import SGDState, sgd_step

    model = build_model("resnet18", faithful=False, dtype="bfloat16")
    p = model.init(jax.random.key(0), jnp.zeros((1, 32, 32, 3)))["params"]
    m = jax.tree.map(jnp.zeros_like, p)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(batch, 32, 32, 3)).astype(np.float32))
    y = jnp.asarray(rng.integers(0, 10, batch).astype(np.int32))
    w = jnp.ones((batch,), jnp.float32)

    def one(p, m):
        def loss_fn(p_):
            return cross_entropy(model.apply({"params": p_}, x), y, w)
        loss, g = jax.value_and_grad(loss_fn)(p)
        p, st = sgd_step(p, SGDState(m), g, lr=0.1, momentum=0.9)
        return p, st.momentum, loss

    def k_steps(p, m, k):
        def body(c, _):
            p_, m_, l = one(*c)
            return (p_, m_), l
        (p, m), ls = jax.lax.scan(body, (p, m), None, length=k)
        return ls.sum()

    f1 = jax.jit(lambda p, m: k_steps(p, m, 1))
    fk = jax.jit(lambda p, m: k_steps(p, m, steps))
    float(f1(p, m)); float(fk(p, m))

    def t(f):
        ts = []
        for _ in range(3):
            t0 = time.perf_counter()
            float(f(p, m))
            ts.append(time.perf_counter() - t0)
        return min(ts)

    return (t(fk) - t(f1)) / (steps - 1)


def main() -> int:
    from dopt.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--trace", default="results/trace_baseline5.json")
    ap.add_argument("--out", default="results/roofline_baseline5.json")
    args = ap.parse_args()

    import jax

    from dopt.presets import get_preset
    from dopt.utils.profiling import device_peak_flops, train_flops_per_sample
    from dopt.models import build_model
    import jax.numpy as jnp

    trace = json.loads(Path(args.trace).read_text())
    rounds = trace.get("rounds_traced", 2)
    dev_ms_round = trace["device_self_time_us"] / 1e3 / rounds

    cfg = get_preset("baseline5")
    w = cfg.data.num_users
    shard = cfg.data.synthetic_train_size // w
    samples_round = w * shard * cfg.gossip.local_ep
    total_batch = w * cfg.gossip.local_bs

    model = build_model("resnet18", faithful=False)
    p0 = model.init(jax.random.key(0), jnp.zeros((1, 32, 32, 3)))["params"]
    tfps = train_flops_per_sample(
        lambda p, x: model.apply({"params": p}, x), p0, (32, 32, 3))
    kind, peak = device_peak_flops()

    sps_dev = samples_round / (dev_ms_round / 1e3)
    flops_sec = sps_dev * tfps

    w1_step = measure_w1_bound(total_batch)
    steps_round = -(-shard // cfg.gossip.local_bs) * cfg.gossip.local_ep
    w1_ms_round = w1_step * steps_round * 1e3
    w1_sps = samples_round / (w1_ms_round / 1e3)

    out = {
        "preset": "baseline5",
        "model": "resnet18", "workers": w, "local_bs": cfg.gossip.local_bs,
        "device_kind": kind,
        "train_flops_per_sample": round(tfps),
        "measured": {
            "device_ms_per_round": round(dev_ms_round, 1),
            "samples_per_sec_device_basis": round(sps_dev, 1),
            "model_tflops_per_sec": round(flops_sec / 1e12, 2),
            "mfu_vs_bf16_peak": round(flops_sec / peak, 4) if peak else None,
            "source": f"{args.trace} (XLA device self-time; host noise "
                      "excluded)",
        },
        "fleet_independence_bound": {
            "w1_ms_per_step": round(w1_step * 1e3, 2),
            "w1_ms_per_round_equiv": round(w1_ms_round, 1),
            "w1_samples_per_sec": round(w1_sps, 1),
            "w1_mfu_vs_bf16_peak": round(w1_sps * tfps / peak, 4)
                                    if peak else None,
            "measured_fraction_of_bound": round(w1_ms_round / dev_ms_round, 3),
            "method": "single weight set, batch = W*local_bs, marginal "
                      "per-step time of a fused scan — removes the "
                      "per-worker-weights cost entirely; no stacked "
                      "fleet can exceed this throughput",
        },
        "conv_pct_of_device": next(
            (c["pct_of_device"] for c in trace["device_categories"]
             if c["op_type"] == "conv_general_dilated"), None),
        "history_vmap_r3_device_ms_per_round": 2754.4,
        "conclusion": (
            "The grouped-stacked fleet forward (worker axis in conv "
            "feature groups) runs the 32-model round at "
            f"{dev_ms_round:.0f} ms of device time vs 2754 ms for the "
            "vmapped per-worker path (r3).  Round 5's per-layer table "
            "(results/roofline_layers_baseline5.json) showed the "
            "grouped-conv penalty is LANE-BATCH STARVATION, not a "
            "hardware ceiling: at the old local_bs=64 the "
            "stride-2/1x1/deep-stage convs ran at ~0.35x of their "
            "single-weight-set rate, recovering to ~0.9x at 128 "
            "rows/lane.  With local_bs=128 in the preset the fleet "
            "program stands at the fraction of the single-weight-set "
            "bound reported in "
            "fleet_independence_bound.measured_fraction_of_bound."),
    }
    Path(args.out).write_text(json.dumps(out, indent=2) + "\n")
    print(json.dumps({k: out[k] for k in ("measured",
                                          "fleet_independence_bound")},
                     indent=1))
    print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
