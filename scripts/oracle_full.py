"""Full-horizon sequential torch-CPU oracle for baseline2.

Runs the same oracle as scripts/time_to_target.py's truncated column,
but for the full horizon the TPU run needed (57 rounds + the 58th
consensus, matching acc_by_round[57] on the TPU side), and writes
results/oracle_full_baseline2.json.  ~70 min of single-core torch —
run once, merge into time_to_target.json via --merge.

Usage:
    python scripts/oracle_full.py [--rounds 57] [--merge]
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
sys.path.insert(0, str(Path(__file__).resolve().parent))


def main() -> int:
    from dopt.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=57,
                    help="oracle horizon k; compares vs TPU acc_by_round[k]")
    ap.add_argument("--out", default="results/oracle_full_baseline2.json")
    ap.add_argument("--merge", action="store_true",
                    help="merge an existing --out into time_to_target.json")
    args = ap.parse_args()

    from time_to_target import oracle_baseline

    from dopt.presets import get_preset

    out = Path(args.out)
    ttt_path = Path("results/time_to_target.json")

    if not args.merge:
        om = oracle_baseline(get_preset("baseline2"), args.rounds)
        payload = {"preset": "baseline2",
                   "oracle_rounds_full": om["oracle_rounds"],
                   "oracle_final_acc_full": om["oracle_final_acc"],
                   "oracle_seconds_full": om["oracle_seconds"]}
        out.write_text(json.dumps(payload, indent=2) + "\n")
        print(f"wrote {out}: {payload}")

    # Merge into the time_to_target artifact (idempotent).
    payload = json.loads(out.read_text())
    ttt = json.loads(ttt_path.read_text())
    for r in ttt["results"]:
        if r["preset"] == "baseline2":
            r.update({k: v for k, v in payload.items() if k != "preset"})
            k = payload["oracle_rounds_full"]
            acc = r.get("acc_by_round", [])
            # Written unconditionally: a horizon beyond the TPU run's
            # trajectory yields an explicit null, never a stale value.
            r["tpu_acc_at_full_oracle_round"] = (
                acc[k] if len(acc) > k else None)
            if len(acc) <= k:
                print(f"warning: TPU trajectory has {len(acc)} rounds "
                      f"<= oracle horizon {k}; same-round comparison "
                      "unavailable", file=sys.stderr)
            fa = r.get("final_acc")
            # final_acc can be None (run ended before any eval row);
            # the delta is then an explicit null, not a TypeError.
            r["tpu_final_minus_full_oracle"] = (
                round(fa - payload["oracle_final_acc_full"], 4)
                if fa is not None else None)
    ttt_path.write_text(json.dumps(ttt, indent=2) + "\n")
    print(f"merged into {ttt_path}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
