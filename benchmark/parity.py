"""Is the system's arithmetic the configuration's?  The cell's job, cut
to a few local steps and two rounds (the traffic file's ``parity``), run
by the system and by the plain reference from the same initial
parameters, batches and mixing matrices or client samples, both at
float32 under ``jax.default_matmul_precision("highest")``.

The system's ``compute_dtype`` is forced to float32 for this job: a bf16
configuration's own casts would otherwise be the whole difference.  The
parity job checks the plumbing (neighbours, weights, sampling, batch
order, the step's form, the mix's precision); ``loss_at_round`` guards
the timed program's own precision.
"""

from __future__ import annotations

import time

import jax

from benchmark import adapter, reference

# Largest |system - reference| allowed on any parameter after the job.
# Measured on the v5e (PERF.md, PR 22; 40 runs, nine seeds): 4e-8 to 2e-7
# for FedAvg on Model1, 2e-6 to 7e-6 for the 160-worker ring, 1.1e-5 to
# 1.6e-5 for the 32 ResNet-18s (parameters move by 7e-3 to 4e-2 in the
# job).  A wrong neighbour or client moves parameters by that whole
# movement, one bf16 mix by 8.4e-3 (PERF.md, PR 21), a bf16 cast of a
# ~0.1-sized weight by 4e-4: all fail at 5e-5 with a factor of eight and
# more to spare, and float32 reassociation (fused stacked convolutions,
# fast-variance GroupNorm, two rounds) stays a factor of three under it.
TOLERANCE = 5e-5


def run(cfg, config: dict, traffic: dict) -> dict:
    """Returns ``{"error", "tolerance", "ok", "seconds"}``."""
    t0 = time.perf_counter()
    cut = traffic["parity"]
    pcfg = adapter.parity_config(cfg, traffic)
    forward = reference.load_forward(config["reference"])
    with jax.default_matmul_precision("highest"):
        trainer = adapter.build_trainer(pcfg, traffic)
        init = adapter.initial_params(trainer, traffic)
        rounds = adapter.reference_rounds(trainer, pcfg, traffic,
                                          cut["rounds"])
        trainer.run(rounds=cut["rounds"])
        got = adapter.final_params(trainer, traffic)
        workers = trainer.num_workers
        del trainer          # the fleet's device state, before the reference's
    kw = {"lr": pcfg.optim.lr, "momentum": pcfg.optim.momentum}
    if traffic["engine"] == "gossip":
        want = reference.run_gossip(forward, init, rounds, **kw)
    else:
        want = reference.run_fedavg(forward, init, rounds, workers, **kw)
    error = reference.max_abs_error(got, want)
    moved = reference.max_abs_error(
        want, [init] * len(want) if isinstance(want, list) else init)
    return {"error": error, "tolerance": TOLERANCE,
            "ok": bool(error <= TOLERANCE), "moved": moved,
            "seconds": time.perf_counter() - t0}
