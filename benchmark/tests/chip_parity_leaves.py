"""Where a parity reading comes from, leaf by leaf, at a cell's OWN size
on the chip:

    python3 -m benchmark.tests.chip_parity_leaves --workload <cell> \\
        --seeds <n> [<n> ...] [--set KEY=INT ...] [--drop KEY ...] \\
        [--program-f32]

``--set`` / ``--drop`` change the configuration's integer keys for this
look (``init_seed``, ``seed``).  For each seed, in one process: the parity
job as the cell runs it (the program against the float32 reference at
``highest``), then a witness that shares no code with the program: the
reference itself at jax's default matmul precision (bfloat16 products,
the compute the configuration states) against the reference at
``highest``.  With ``--program-f32``, after every seed's: the program at
float32 compute against the reference.  One JSON line a reading: the
share ``parity`` compares, and the worst leaves, each with its error,
the reference's movement and the place of its worst element.  Nothing
here is part of a benchmark run."""

import argparse
import json
import sys

import jax
import numpy as np

from benchmark import adapter, reference
from benchmark.run import load_cell


def by_leaf(tree) -> dict:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(p): np.asarray(x, np.float32)
            for p, x in flat}


def worst_leaves(got: list, want: list, init, top: int = 6) -> dict:
    """``got`` and ``want``: every worker's tree; ``init``: one tree."""
    g, w, i0 = [by_leaf(t) for t in got], [by_leaf(t) for t in want], \
        by_leaf(init)
    rows = []
    for name, x0 in i0.items():
        errs = [np.abs(a[name] - b[name]) for a, b in zip(g, w)]
        k = int(np.argmax([e.max() for e in errs]))
        e = errs[k]
        rows.append({
            "leaf": name, "error": float(e.max()),
            "moved": max(float(np.abs(b[name] - x0).max()) for b in w),
            "worker": k,
            "at": [int(a) for a in np.unravel_index(int(e.argmax()),
                                                    e.shape)],
            "over_half": int((e > e.max() / 2).sum())})
    error = max(r["error"] for r in rows)
    moved = max(r["moved"] for r in rows)
    rows.sort(key=lambda r: -r["error"])
    return {"error": error, "moved": moved, "compared": error / moved,
            "worst": rows[:top]}


def program_job(cfg, config, traffic):
    """parity.run's program half: (init, rounds, every worker's result)."""
    n = traffic["parity"].get("rounds", 2)
    pcfg = adapter.parity_config(cfg, traffic)
    with jax.default_matmul_precision("highest"):
        trainer = adapter.build_trainer(pcfg, traffic, config)
        init = adapter.initial_params(trainer, traffic)
        rounds = adapter.reference_rounds(trainer, pcfg, traffic, n)
        trainer.run(rounds=n)
        got = adapter.final_params(trainer, traffic)
        del trainer
    return pcfg, init, rounds, got


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--set", nargs="*", default=[])
    ap.add_argument("--drop", nargs="*", default=[])
    ap.add_argument("--program-f32", action="store_true")
    args = ap.parse_args(argv)

    cell = load_cell(args.workload)
    traffic, config = cell["traffic"], dict(cell["config"])
    if traffic["engine"] != "gossip":
        raise SystemExit("a gossip cell: the witness replays run_gossip")
    for key in args.drop:
        config.pop(key, None)
    for kv in args.set:
        key, value = kv.split("=")
        config[key] = int(value)
    adapter.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    objective = reference.load_objective(config["reference"])
    f32 = {**traffic, "parity": {**traffic["parity"],
                                 "compute_dtype": "float32"}}
    base = {"workload": cell["name"],
            "config": {k: config.get(k) for k in ("init_seed", "seed")}}

    def say(**kw):
        print(json.dumps({**base, **kw}), flush=True)

    for seed in args.seeds:
        cfg = adapter.build_config(cell["name"], config, traffic, seed=seed,
                                   chips=cell["chips"])
        pcfg, init, rounds, got = program_job(cfg, config, traffic)
        kw = {"lr": pcfg.optim.lr, "momentum": pcfg.optim.momentum}
        want = reference.run_gossip(objective, init, rounds, **kw)
        say(seed=seed, reading="program_vs_reference",
            **worst_leaves(got, want, init))
        del got
        low = reference.run_gossip(objective, init, rounds, **kw,
                                   precision="default")
        say(seed=seed, reading="reference_default_vs_highest",
            **worst_leaves(low, want, init))
        del low, want, init, rounds
    if args.program_f32:
        for seed in args.seeds:
            cfg = adapter.build_config(cell["name"], config, traffic,
                                       seed=seed, chips=cell["chips"])
            try:
                pcfg, init, rounds, got = program_job(cfg, config, f32)
            except Exception as e:       # a reading, not a crash: say so
                say(seed=seed, reading="program_f32_vs_reference",
                    failed=f"{type(e).__name__}: {str(e)[:300]}")
                continue
            kw = {"lr": pcfg.optim.lr, "momentum": pcfg.optim.momentum}
            want = reference.run_gossip(objective, init, rounds, **kw)
            say(seed=seed, reading="program_f32_vs_reference",
                **worst_leaves(got, want, init))
            del got, want, init, rounds
    return 0


if __name__ == "__main__":
    sys.exit(main())
