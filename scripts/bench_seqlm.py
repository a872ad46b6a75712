"""Sequence-parallel LM throughput: tokens/sec on the current device(s).

Measures steady-state training throughput of the ``seqlm`` preset
(decoder-only TransformerLM, ring attention, sequence axis sharded over
all devices).  On a single chip the ring degenerates to one block (same
code path, no hops); on an N-device mesh the KV pairs rotate over ICI.
There is no reference counterpart (the reference has no sequence axis);
the number is the framework's own long-context baseline.

Point mode prints one JSON line:
    python scripts/bench_seqlm.py [--steps N] [--seq-len L] [--kv-chunk C]

Sweep mode (``--sweep``) doubles seq_len until the chip OOMs, with and
without flash-style KV chunking (``SeqLMConfig.kv_chunk`` — the knob
that turns the per-block score memory from O(block²) into
O(block·chunk)), records tokens/sec + peak HBM per point, and writes
``results/seqlm_bench.json`` with the longest trainable context per
branch.  Each point runs in a SUBPROCESS so an OOM cannot poison the
sweep's runtime state.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import subprocess
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def run_point(args) -> int:
    import jax

    from dopt.engine import SeqLMTrainer
    from dopt.presets import get_preset
    from dopt.utils.metrics import trimmed_stats

    cfg = get_preset("seqlm")
    cfg = cfg.replace(seqlm=dataclasses.replace(
        cfg.seqlm, steps=args.steps, seq_len=args.seq_len, batch=args.batch,
        attn=args.attn, kv_chunk=args.kv_chunk,
        log_every=max(args.steps // 3, 1)))
    tr = SeqLMTrainer(cfg)
    tr.run(steps=3)                       # compile + warmup
    tokens = args.steps * args.batch * args.seq_len
    tps = []
    total = 0.0
    for _ in range(max(args.repeats, 1)):
        t0 = time.time()
        tr.run(steps=args.steps)
        jax.block_until_ready(tr.params)
        elapsed = time.time() - t0
        total += elapsed
        tps.append(tokens / elapsed)
    med, spread, _ = trimmed_stats(tps)
    # Standard bench JSON-line schema (metric/value/unit/device_kind +
    # the trimmed-median wall reduction), so the ring-attention LM line
    # drops into the same tooling as bench.py's headline lines
    # (ROADMAP lever 4 groundwork: the seqlm workload as a first-class
    # headline bench).
    out = {
        "metric": "seqlm_tokens_per_sec",
        "value": round(med, 1),
        "unit": "tokens/sec",
        "device_kind": str(jax.devices()[0].device_kind),
        "spread_pct": round(spread, 2),
        "measured_windows": len(tps),
        "measured_seconds": round(total, 2),
        "steps_per_window": args.steps,
        "attn": args.attn,
        "seq_len": args.seq_len,
        "batch": args.batch,
        "kv_chunk": args.kv_chunk,
        "mesh_devices": tr.mesh.size,
        "params": tr.param_count,
        "final_loss": round(tr.history.last()["loss"], 4),
        # Back-compat alias for pre-schema consumers of this script.
        "device": str(jax.devices()[0].device_kind),
    }
    # Shared occupancy helper (dopt.utils.profiling.device_memory_stats:
    # backend allocator stats on TPU/GPU, host-RSS fallback on CPU) —
    # the same peak-HBM column bench.py's headline line carries, so the
    # seqlm line is always comparable and always present.
    from dopt.utils.profiling import device_memory_stats

    mem = device_memory_stats()
    if mem is not None:
        out["peak_hbm_gb"] = round(mem["peak_bytes"] / 2**30, 3)
        out["hbm_source"] = mem["source"]
    print(json.dumps(out))
    return 0


def run_sweep(args) -> int:
    """Double seq_len until OOM, for kv_chunk in (0, --kv-chunk)."""
    if args.attn != "ring":
        print(f"--sweep requires --attn ring (kv_chunk only applies to "
              f"ring attention, got {args.attn!r})", file=sys.stderr)
        return 2
    points, longest = [], {}
    for kv in (0, args.kv_chunk):
        label = f"kv_chunk={kv}" if kv else "no chunking (O(block²) scores)"
        for exp in range(100):
            seq = args.seq_len << exp
            if seq > args.max_seq_len:
                break
            cmd = [sys.executable, __file__, "--steps", str(args.steps),
                   "--seq-len", str(seq), "--batch", str(args.batch),
                   "--attn", args.attn, "--kv-chunk", str(kv)]
            try:
                r = subprocess.run(cmd, capture_output=True, text=True,
                                   timeout=1800)
            except subprocess.TimeoutExpired as e:
                # A wedged point (e.g. runtime hang at the OOM boundary)
                # ends its branch but must not lose the sweep so far.
                points.append({"seq_len": seq, "kv_chunk": kv,
                               "status": "timeout",
                               "stderr_tail": str(e)[-400:]})
                print(f"[sweep] {label} seq_len={seq}: TIMEOUT", flush=True)
                break
            line = next((ln for ln in r.stdout.splitlines()
                         if ln.startswith("{")), None)
            if r.returncode != 0 or line is None:
                oom = ("RESOURCE_EXHAUSTED" in r.stderr
                       or "out of memory" in r.stderr.lower())
                points.append({"seq_len": seq, "kv_chunk": kv,
                               "status": "oom" if oom else "failed",
                               "stderr_tail": r.stderr.strip()[-400:]})
                print(f"[sweep] {label} seq_len={seq}: "
                      f"{'OOM' if oom else 'FAILED'}", flush=True)
                break
            p = json.loads(line)
            p["status"] = "ok"
            points.append(p)
            longest[f"kv_chunk_{kv}"] = seq
            print(f"[sweep] {label} seq_len={seq}: "
                  f"{p['value']:,.0f} tok/s"
                  + (f", peak HBM {p['peak_hbm_gb']} GB"
                     if "peak_hbm_gb" in p else ""), flush=True)
    payload = {
        "suite": "seqlm long-context sweep",
        "attn": args.attn,
        "batch": args.batch,
        "steps_per_point": args.steps,
        "longest_trainable_seq_len": longest,
        "points": points,
    }
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(payload, indent=2) + "\n")
    print(f"wrote {out}")
    return 0


def main() -> int:
    from dopt.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--repeats", type=int, default=1,
                    help="independent measured windows; the reported "
                         "value is their min/max-trimmed median "
                         "(dopt.utils.metrics.trimmed_stats, the same "
                         "variance hardening bench.py uses)")
    ap.add_argument("--seq-len", type=int, default=2048)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--attn", default="ring", choices=["ring", "ulysses"])
    ap.add_argument("--kv-chunk", type=int, default=0,
                    help="flash-style KV chunk (0 = full-block scores); "
                         "in --sweep mode, the chunked branch's size")
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--max-seq-len", type=int, default=1 << 20)
    ap.add_argument("--out", default="results/seqlm_bench.json")
    args = ap.parse_args()
    if args.sweep:
        if not args.kv_chunk:
            args.kv_chunk = 512
        return run_sweep(args)
    return run_point(args)


if __name__ == "__main__":
    raise SystemExit(main())
