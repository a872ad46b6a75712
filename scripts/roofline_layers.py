"""Per-layer grouped-conv roofline for baseline5 (VERDICT r4 item 5).

For every distinct conv shape in the grouped-stacked ResNet-18 fleet
program (32 workers as feature_group_count=32), measures achieved
training TFLOP/s (fwd + bwd, 3x fwd accounting matched by actual
autodiff work) two ways on the real chip:

* grouped   — the fleet execution: x [B, H, W, 32*Cin], kernel
              [kh, kw, Cin, 32*Cout], feature_group_count=32.
* single    — the fleet-INDEPENDENCE bound term: one weight set at the
              same total sample count: x [32*B, H, W, Cin] (groups=1).

The ratio column shows exactly which layers pay a grouped-conv penalty
and which hit the same hardware ceiling either way — the committed
evidence behind roofline_baseline5.json's measured_fraction_of_bound.
Also probes the two worst layers with lane-batch 128 (local_bs
128/lane, VERDICT's suggested recovery lever).

Writes results/roofline_layers_baseline5.json.
Usage: python scripts/roofline_layers.py [--iters 30]
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

# Per-preset fleet geometry: workers (feature groups), per-lane batch,
# and the distinct conv shapes (name, count, H, Cin, Cout, kh, stride;
# input spatial HxH).
PRESETS = {
    # baseline5: ResNet-18 stage structure at 32x32 CIFAR inputs
    # (stage_sizes (2,2,2,2)).
    "baseline5": {
        "workers": 32, "lane_batch": 64,
        "layers": [
            ("stem",        1, 32,   3,  64, 3, 1),
            ("s0.conv",     4, 32,  64,  64, 3, 1),
            ("s1.down",     1, 32,  64, 128, 3, 2),
            ("s1.conv",     3, 16, 128, 128, 3, 1),
            ("s1.proj",     1, 32,  64, 128, 1, 2),
            ("s2.down",     1, 16, 128, 256, 3, 2),
            ("s2.conv",     3,  8, 256, 256, 3, 1),
            ("s2.proj",     1, 16, 128, 256, 1, 2),
            ("s3.down",     1,  8, 256, 512, 3, 2),
            ("s3.conv",     3,  4, 512, 512, 3, 1),
            ("s3.proj",     1,  8, 256, 512, 1, 2),
        ],
    },
    # headline: bench.py's Model1 (fc layers as VALID convs, exactly the
    # grouped-stacked program's shapes).  conv1 is the documented sore
    # spot: 1 input channel per group — every formulation tried (direct,
    # grouped-1x1-over-patches, batched einsum) lands within ~10% of the
    # same cost; the time is activation-layout movement, not math.
    "headline": {
        "workers": 6, "lane_batch": 128,
        "layers": [
            ("conv1",  1, 28,   1,  32, 5, 1),
            ("conv2",  1, 14,  32,  64, 5, 1),
            ("fc1",    1,  7,  64, 512, 7, 1),   # VALID 7x7 -> 1x1
            ("fc2",    1,  1, 512,  10, 1, 1),
        ],
    },
}

W = 32          # set per-preset in main()
B = 64


def conv_flops(h, cin, cout, k, stride, batch, groups, pad="SAME"):
    ho = h // stride if pad == "SAME" else h - k + 1
    macs = batch * ho * ho * cout * k * k * cin * groups
    return 2 * macs          # fwd FLOPs; training = 3x (fwd+bwd)


def _device_seconds(blk) -> float:
    """Profiler device self-time of ``blk()`` in seconds.  Roofline
    numbers are committed artifacts, so a degraded profiler stack
    (which ``device_stats_of`` tolerates for bench) must fail LOUDLY
    here — NaN-derived TFLOP/s in the JSON would be worse than no run."""
    from dopt.utils.profiling import device_stats_of

    stats = device_stats_of(blk)
    if "warning" in stats:
        raise RuntimeError(
            "roofline needs the profiler device-time basis but it "
            f"degraded: {stats['warning']}")
    return stats["device_self_time_us"] / 1e6


def measure(fn, args, iters):
    """Per-iteration time of fwd + dK + dX (the full 3x-fwd training
    cost the table's FLOP accounting assumes), measured as ONE jitted
    ``lax.scan`` of ``iters`` DEPENDENT steps — each step feeds its
    gradients back into the next step's inputs, so no iteration can be
    elided, reordered, or overlapped (a naive dispatch loop times the
    enqueue, not the work)."""
    import jax

    def run_impl(k, x, ct):
        # ct enters as a jit ARGUMENT (a closure constant this large
        # is baked into the program).
        def body(carry, _):
            k_, x_ = carry
            dk, dx = jax.grad(fn, argnums=(0, 1))(k_, x_, ct)
            return (k_ + 1e-4 * dk, x_ + 1e-4 * dx), ()

        return jax.lax.scan(body, (k, x), None, length=iters)[0]

    run = jax.jit(run_impl)
    r = run(*args)
    jax.block_until_ready(r)
    # Wall clock is noisier than device time for sub-second intervals;
    # the profiler's device self-time is the basis here.
    def blk():
        jax.block_until_ready(run(*args))

    return _device_seconds(blk) / iters


def bench_layer(h, cin, cout, k, stride, *, workers=W, lane_batch=B,
                iters=30, pad="SAME"):
    import jax
    import jax.numpy as jnp
    import numpy as np

    W_ = workers
    rng = np.random.default_rng(0)
    ho = h // stride if pad == "SAME" else h - k + 1
    kern_g = jnp.asarray(rng.normal(size=(k, k, cin, W_ * cout)) * 0.05,
                         jnp.bfloat16)
    x_g = jnp.asarray(rng.normal(size=(lane_batch, h, h, W_ * cin)),
                      jnp.bfloat16)
    # Random fixed cotangent: with a plain sum loss the cotangent is
    # all-ones and XLA legally simplifies BOTH backward convolutions to
    # cheap reductions (measured >chip-peak "TFLOP/s"); a random c
    # keeps dX and dK honest full convolutions.
    c_g = jnp.asarray(rng.normal(size=(lane_batch, ho, ho, W_ * cout)),
                      jnp.bfloat16)

    def f_grouped(kern, x, ct):
        out = jax.lax.conv_general_dilated(
            x, kern, (stride, stride), pad,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=W_)
        return jnp.sum((out * ct).astype(jnp.float32))

    kern_s = jnp.asarray(rng.normal(size=(k, k, cin, cout)) * 0.05,
                         jnp.bfloat16)
    x_s = jnp.asarray(rng.normal(size=(W_ * lane_batch, h, h, cin)),
                      jnp.bfloat16)
    c_s = jnp.asarray(rng.normal(size=(W_ * lane_batch, ho, ho, cout)),
                      jnp.bfloat16)

    def f_single(kern, x, ct):
        out = jax.lax.conv_general_dilated(
            x, kern, (stride, stride), pad,
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        return jnp.sum((out * ct).astype(jnp.float32))

    t_g = measure(f_grouped, (kern_g, x_g, c_g), iters)
    t_s = measure(f_single, (kern_s, x_s, c_s), iters)
    fl = 3 * conv_flops(h, cin, cout, k, stride, lane_batch, W_, pad)
    return fl, fl / t_g / 1e12, fl / t_s / 1e12


def bench_update(params_total, iters, *, lr=0.01, mu=0.5):
    """Device time per momentum-SGD update of a ``params_total``-element
    fleet parameter vector (the weight-update phase: 3 reads, 2 writes,
    zero FLOP reuse — pure HBM bandwidth), measured as one jitted scan
    of DEPENDENT steps exactly like ``measure``.  This is the
    non-conv round fraction ISSUE 5 shards away (update_sharding=
    "scatter" runs it on 1/D of the flat tree), committed here so
    regressions in the update share are attributable from the
    artifact."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(1)
    p = jnp.asarray(rng.normal(size=params_total).astype(np.float32))
    m = jnp.zeros_like(p)
    g = jnp.asarray(rng.normal(size=params_total).astype(np.float32))

    def run_impl(p0, m0, gg):
        def body(carry, _):
            p_, m_ = carry
            buf = mu * m_ + gg
            return (p_ - lr * buf, buf), ()

        return jax.lax.scan(body, (p0, m0), None, length=iters)[0]

    run = jax.jit(run_impl)
    jax.block_until_ready(run(p, m, g))

    def blk():
        jax.block_until_ready(run(p, m, g))

    return _device_seconds(blk) / iters


def fleet_param_count(geom) -> int:
    """Conv-layer fleet parameter count for a preset's geometry table
    (weights + biases, × workers).  Exact for the headline Model1
    (1.66M × 6); for baseline5 it covers the conv stack the table
    describes (the norm/fc tail is <1% of the ResNet tree)."""
    per_worker = sum(count * (k * k * cin * cout + cout)
                     for _, count, _, cin, cout, k, _ in geom["layers"])
    return geom["workers"] * per_worker


def main() -> int:
    from dopt.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=30)
    ap.add_argument("--preset", default="baseline5",
                    choices=sorted(PRESETS))
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    geom = PRESETS[args.preset]
    workers, lane_b = geom["workers"], geom["lane_batch"]
    out_path = (args.out
                or f"results/roofline_layers_{args.preset}.json")

    import jax

    from dopt.utils.profiling import device_peak_flops

    kind, peak = device_peak_flops()
    rows = []
    for name, count, h, cin, cout, k, stride in geom["layers"]:
        pad = "VALID" if name.startswith("fc") else "SAME"
        fl, tf_g, tf_s = bench_layer(h, cin, cout, k, stride,
                                     workers=workers, lane_batch=lane_b,
                                     iters=args.iters, pad=pad)
        rows.append({
            "layer": name, "count": count, "spatial": h,
            "cin": cin, "cout": cout, "kernel": k, "stride": stride,
            "train_flops_fleet": fl,
            "grouped_tflops": round(tf_g, 2),
            "single_tflops": round(tf_s, 2),
            "grouped_over_single": round(tf_g / tf_s, 3),
            "grouped_mfu": round(tf_g * 1e12 / peak, 4) if peak else None,
        })
        print(f"{name:10s} {h:3}px {cin:4}->{cout:<4} k{k} s{stride}: "
              f"grouped {tf_g:6.1f} TF/s, single {tf_s:6.1f} TF/s "
              f"(ratio {tf_g/tf_s:.2f})", flush=True)

    # Weighted fleet summary: time-weighted by per-layer grouped cost.
    tot_fl = sum(r["train_flops_fleet"] * r["count"] for r in rows)
    tot_tg = sum(r["train_flops_fleet"] * r["count"]
                 / (r["grouped_tflops"] * 1e12) for r in rows)
    tot_ts = sum(r["train_flops_fleet"] * r["count"]
                 / (r["single_tflops"] * 1e12) for r in rows)
    summary = {
        "conv_stack_grouped_tflops": round(tot_fl / tot_tg / 1e12, 2),
        "conv_stack_single_tflops": round(tot_fl / tot_ts / 1e12, 2),
        "conv_stack_grouped_fraction_of_single": round(tot_ts / tot_tg, 3),
    }
    print("conv stack:", summary, flush=True)

    # Recovery probe: the two worst ratio layers at 2x the lane batch
    # (the local_bs lever).
    probes = []
    if lane_b < 128:
        worst = sorted(rows, key=lambda r: r["grouped_over_single"])[:2]
        for r in worst:
            fl, tf_g, tf_s = bench_layer(
                r["spatial"], r["cin"], r["cout"], r["kernel"],
                r["stride"], workers=workers, lane_batch=2 * lane_b,
                iters=args.iters,
                pad=("VALID" if r["layer"].startswith("fc") else "SAME"))
            probes.append({"layer": r["layer"], "lane_batch": 2 * lane_b,
                           "grouped_tflops": round(tf_g, 2),
                           "single_tflops": round(tf_s, 2),
                           "grouped_over_single": round(tf_g / tf_s, 3)})
            print(f"probe {r['layer']} @ lane_batch={2*lane_b}: grouped "
                  f"{tf_g:.1f} single {tf_s:.1f} "
                  f"(ratio {tf_g/tf_s:.2f})", flush=True)

    # Update-phase share (ISSUE 5 satellite): the per-step weight
    # update over the full fleet tree, alongside the per-layer conv
    # compute — the committed artifact that makes regressions in the
    # NON-conv round fraction attributable.  Per-step share equals
    # per-round share (both scale with step count).
    fleet_params = fleet_param_count(geom)
    upd_s = bench_update(fleet_params, args.iters)
    conv_s = sum(r["train_flops_fleet"] * r["count"]
                 / (r["grouped_tflops"] * 1e12) for r in rows)
    update_phase = {
        "fleet_params": fleet_params,
        "update_us_per_step": round(upd_s * 1e6, 2),
        "conv_us_per_step": round(conv_s * 1e6, 2),
        "update_share_of_step": round(upd_s / (upd_s + conv_s), 4),
        "update_gbps": round(5 * 4 * fleet_params / upd_s / 1e9, 1),
        "note": ("momentum-SGD update of the fleet tree (3 reads + 2 "
                 "writes per element, dependent-step scan, profiler "
                 "device self-time) vs the conv stack's per-step time "
                 "from the table above; update_sharding='scatter' "
                 "divides the update work by the mesh size"),
    }
    print(f"update phase: {upd_s*1e6:.1f} us/step over "
          f"{fleet_params/1e6:.2f}M params "
          f"({update_phase['update_share_of_step']*100:.1f}% of "
          f"conv+update step time)", flush=True)

    payload = {
        "suite": f"roofline_layers_{args.preset}",
        "device": str(jax.devices()[0]),
        "device_kind": kind,
        "bf16_peak_tflops": peak / 1e12 if peak else None,
        "workers": workers, "lane_batch": lane_b,
        "note": ("fwd+dK+dX achieved TFLOP/s per distinct conv shape "
                 "(dependent-step scan, random cotangent, profiler "
                 "device self-time); 'single' = one weight set at the "
                 "same total sample count (the fleet-independence "
                 "bound term).  SAME-padding FLOPs are nominal "
                 "k^2*Cin*H'*W' — XLA skips padded taps, so small-"
                 "spatial rows overstate achieved TFLOP/s by up to "
                 "~1.4x; the grouped/single ratio cancels that."),
        "layers": rows,
        "summary": summary,
        "update_phase": update_phase,
        "double_lane_batch_probe": probes,
    }
    out = Path(out_path)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(payload, indent=2) + "\n")
    print(f"wrote {out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
