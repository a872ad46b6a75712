"""Is the system's arithmetic the configuration's?  The cell's job, cut
as the traffic file's ``parity`` says, run by the system and by the plain
reference from the same initial parameters, batches and mixing matrices
or client samples, under ``jax.default_matmul_precision("highest")``.

The cut is data.  ``rounds`` (2 where absent), ``local_ep``,
``steps_per_epoch``, ``local_bs``, ``lr`` and ``compute_dtype`` are each
optional, and what is absent is the cell's own: an empty cut is two
rounds of the timed program at the timed sizes and the stated
precision.  The shipped mixes state ``"compute_dtype":
"float32"``: a bf16 configuration's own casts would otherwise be the
whole difference, so their parity job checks the plumbing (neighbours,
weights, sampling, batch order, the step's form, the mix's precision)
and ``loss_at_round`` guards the timed program's own precision.

The tolerance is data too: the configuration file MAY carry
``"parity_tolerance": {"value": ..., "of": "abs" | "moved", "why":
"..."}``.  ``abs`` holds the largest parameter difference itself to
``value``; ``moved`` holds it as a share of the largest movement of the
reference's parameters over the job.  ``why`` (one line, at most 200
characters: the readings the value was set from) is required and is
printed with every comparison.  Absent, ``TOLERANCE`` absolute.
"""

from __future__ import annotations

import math
import time

import jax

from benchmark import adapter, reference

# Largest |system - reference| allowed on any parameter after the job.
# Measured on the v5e at each cell's own size (PERF.md, PR 27; 15 seeds
# a one-chip cell, 5 on four chips): sound runs read 3e-8 to 1.7e-6 for
# FedAvg on Model1, 7e-7 to 8.5e-6 for the 160-worker ring, 9.8e-6 to
# 1.9e-5 for the 32 ResNet-18s (parameters move by 7e-3 to 3e-2 in the
# job).  The control, the system's compute at bfloat16, reads 6.8e-5 to
# 9.5e-5 on the ring, 8.6e-5 to 1.3e-4 on ResNet-18 and 2.4e-4 to 2.5e-3
# on FedAvg; an exchange left out 1.8e-3 to 1.8e-2, a state left unchanged
# the whole movement, one bf16 mix 8.4e-3 (PERF.md, PR 21).  5e-5 stands a
# factor of 2.7 above the largest sound reading and 1.4 under the smallest
# control.
TOLERANCE = 5e-5
TOLERANCE_WHY = ("sound runs read 3e-8 to 1.9e-5 on the v5e, the bf16-compute "
                 "control 6.8e-5 and more, an exchange left out 1.8e-3 and more "
                 "(PERF.md, PR 27)")


def tolerance(config: dict) -> dict:
    """``{"value", "of", "why"}``: the configuration's own
    ``parity_tolerance`` or the default."""
    if "parity_tolerance" not in config:
        return {"value": TOLERANCE, "of": "abs", "why": TOLERANCE_WHY}
    tol = config["parity_tolerance"]
    why = tol.get("why")
    if (not isinstance(why, str) or not 1 <= len(why) <= 200
            or "\n" in why or "\t" in why):
        raise ValueError(
            "parity_tolerance needs a 'why' of 1 to 200 characters on one "
            "line: the readings its value was set from")
    if set(tol) != {"value", "of", "why"} or tol["of"] not in ("abs", "moved"):
        raise ValueError(
            "parity_tolerance is {'value': number, 'of': 'abs' | 'moved', "
            f"'why': ...}}, not {tol!r}")
    return {"value": float(tol["value"]), "of": tol["of"], "why": why}


def run(cfg, config: dict, traffic: dict) -> dict:
    """Returns ``{"error", "moved", "compared", "tolerance", "of", "why",
    "ok", "seconds"}``: ``compared`` is the number held to ``tolerance``,
    the error itself or its share of ``moved``."""
    t0 = time.perf_counter()
    n_rounds = traffic["parity"].get("rounds", 2)
    tol = tolerance(config)
    pcfg = adapter.parity_config(cfg, traffic)
    objective = reference.load_objective(config["reference"])
    with jax.default_matmul_precision("highest"):
        trainer = adapter.build_trainer(pcfg, traffic, config)
        init = adapter.initial_params(trainer, traffic)
        rounds = adapter.reference_rounds(trainer, pcfg, traffic, n_rounds)
        trainer.run(rounds=n_rounds)
        got = adapter.final_params(trainer, traffic)
        del trainer          # the fleet's device state, before the reference's
    kw = {"lr": pcfg.optim.lr, "momentum": pcfg.optim.momentum}
    if traffic["engine"] == "gossip":
        want = reference.run_gossip(objective, init, rounds, **kw)
    else:
        want = reference.run_fedavg(objective, init, rounds, **kw)
    error = reference.max_abs_error(got, want)
    moved = reference.max_abs_error(
        want, [init] * len(want) if isinstance(want, list) else init)
    compared = (error if tol["of"] == "abs"
                else error / moved if moved > 0 else math.inf)
    return {"error": error, "moved": moved, "compared": compared,
            "tolerance": tol["value"], "of": tol["of"], "why": tol["why"],
            "ok": bool(compared <= tol["value"]),
            "seconds": time.perf_counter() - t0}
