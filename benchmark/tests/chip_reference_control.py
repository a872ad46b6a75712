"""The readings a parity limit stands between, for a gossip cell whose
program has no working control of its own (a decoder on the fused expert
kernels refuses ``param_dtype bfloat16`` at trace time), at the cell's
OWN size on the chip:

    python3 -m benchmark.tests.chip_reference_control --workload <cell> \\
        --seeds <n> [<n> ...] [--rehearse]

For each seed, ``benchmark.parity.run`` three times in one process, so
that every reading is the harness's own comparison:

* ``sound``: the parity job as the cell runs it;
* ``exchange_left_out``: the round program mixes with the identity;
* ``reference_bf16``: the plain reference one precision below the
  configuration's float32 parameters, in the program's place: its
  parameters, momentum and mix in bfloat16 and its products at bfloat16
  (``run_gossip_below``).  The program's trainer is built (it draws the
  initial parameters and the batches) and never run.

One JSON line a reading.  ``--rehearse`` (``JAX_PLATFORMS=cpu``) runs the
configuration's toy.  Nothing here is part of a benchmark run."""

import argparse
import json
import sys

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import adapter, parity, reference
from benchmark.run import load_cell
from benchmark.tests.test_faults import exchange_left_out


def run_gossip_below(objective, init_params, rounds, *, lr, momentum):
    """``reference.run_gossip`` with the parameters, the momentum, the mix
    and the matmul products in bfloat16."""
    def bf16(tree):
        return jax.tree.map(lambda x: np.asarray(x).astype(jnp.bfloat16),
                            tree)

    with jax.default_matmul_precision("bfloat16"):
        step = reference.make_step(objective, lr=lr, momentum=momentum)
        n = rounds[0]["w"].shape[0]
        params = [bf16(init_params)] * n
        bufs = [jax.tree.map(np.zeros_like, params[0])] * n
        for r in rounds:
            w = jnp.asarray(r["w"], jnp.bfloat16)
            params = reference._leafwise(
                lambda xs: reference._mix_leaf(w, xs), params)
            for i in range(n):
                params[i], bufs[i] = reference._local_steps(
                    step, params[i], bufs[i], r["bx"][i], r["by"][i],
                    r["bw"][i])
        return params


class NotRun:
    """The program's trainer, built and never run."""

    def __init__(self, inner):
        self._inner = inner

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def run(self, rounds):
        return []


def reference_below(cfg, config, traffic) -> dict:
    """``parity.run`` with ``run_gossip_below`` in the program's place.
    Its result is made after the program's trainer is let go, when the
    float32 reference runs: ``got`` is filled then."""
    seen, got = {}, []
    patched = {name: getattr(adapter, name) for name in
               ("build_trainer", "initial_params", "reference_rounds",
                "final_params")}
    run_gossip = reference.run_gossip

    def initial_params(trainer, traffic):
        seen["init"] = patched["initial_params"](trainer, traffic)
        return seen["init"]

    def reference_rounds(trainer, pcfg, traffic, n):
        seen["rounds"] = patched["reference_rounds"](trainer, pcfg, traffic,
                                                     n)
        seen["kw"] = {"lr": pcfg.optim.lr, "momentum": pcfg.optim.momentum}
        return seen["rounds"]

    def want_then_below(objective, init, rounds, **kw):
        want = run_gossip(objective, init, rounds, **kw)
        got.extend(run_gossip_below(objective, seen["init"], seen["rounds"],
                                    **seen["kw"]))
        return want

    adapter.build_trainer = lambda c, t, f: NotRun(
        patched["build_trainer"](c, t, f))
    adapter.initial_params = initial_params
    adapter.reference_rounds = reference_rounds
    adapter.final_params = lambda trainer, traffic: got
    reference.run_gossip = want_then_below
    try:
        return parity.run(cfg, config, traffic)
    finally:
        for name, fn in patched.items():
            setattr(adapter, name, fn)
        reference.run_gossip = run_gossip


def with_trainer(wrap):
    def job(cfg, config, traffic):
        build = adapter.build_trainer
        adapter.build_trainer = lambda c, t, f: wrap(build(c, t, f))
        try:
            return parity.run(cfg, config, traffic)
        finally:
            adapter.build_trainer = build
    return job


READINGS = {"sound": parity.run,
            "exchange_left_out": with_trainer(exchange_left_out),
            "reference_bf16": reference_below}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--readings", nargs="+", default=list(READINGS),
                    choices=list(READINGS))
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    cell = load_cell(args.workload)
    traffic, config = cell["traffic"], cell["config"]
    if traffic["engine"] != "gossip":
        raise SystemExit("a gossip cell: the control replays run_gossip")
    adapter.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    for seed in args.seeds:
        cfg = adapter.build_config(cell["name"], config, traffic, seed=seed,
                                   chips=cell["chips"])
        if args.rehearse:
            cfg = adapter.rehearsal_config(cfg, traffic,
                                           config.get("rehearsal"))
        for reading in args.readings:
            got = READINGS[reading](cfg, config, traffic)
            print(json.dumps({
                "workload": cell["name"], "seed": config.get("seed", seed),
                "reading": reading, "device": jax.devices()[0].device_kind,
                **{k: got[k] for k in ("error", "moved", "compared",
                                       "tolerance", "ok", "seconds")}}),
                flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
