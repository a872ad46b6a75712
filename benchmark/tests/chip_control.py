"""The readings a parity limit stands between, at a cell's OWN size on
the chip (``test_faults.py`` is the same at a size a test run can hold):

    python3 -m benchmark.tests.chip_control --workload <cell> --seeds <n> [<n> ...]

For each seed, the parity job three times in one process: sound; the
control (the system's compute one precision below the cut's float32:
bfloat16); the exchange left out of the round program.  One JSON line a
reading; nothing here is part of a benchmark run."""

import argparse
import json
import sys

from benchmark import adapter, parity
from benchmark.run import load_cell
from benchmark.tests.test_faults import exchange_left_out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    import jax

    cell = load_cell(args.workload)
    traffic, config = cell["traffic"], cell["config"]
    adapter.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    bf16 = {**traffic, "parity": {**traffic["parity"],
                                  "compute_dtype": "bfloat16"}}
    build = adapter.build_trainer
    for seed in args.seeds:
        cfg = adapter.build_config(cell["name"], config, traffic, seed=seed,
                                   chips=cell["chips"])
        for reading, tr, wrap in (("sound", traffic, None),
                                  ("control_bf16", bf16, None),
                                  ("exchange_left_out", traffic,
                                   exchange_left_out)):
            if wrap is not None and traffic["engine"] != "gossip":
                continue
            adapter.build_trainer = (
                build if wrap is None
                else lambda c, t, f, wrap=wrap: wrap(build(c, t, f)))
            try:
                got = parity.run(cfg, config, tr)
            finally:
                adapter.build_trainer = build
            print(json.dumps({
                "workload": cell["name"], "seed": seed, "reading": reading,
                "device": jax.devices()[0].device_kind,
                **{k: got[k] for k in ("error", "moved", "tolerance", "ok",
                                       "seconds")}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
