"""Time the stacked reference CNN's conv1 + bias + 2×2 max-pool alone, in
each form ``dopt/models/zoo.py`` ``_conv1_stacked`` chooses between, on
the chip:

    chiprun -- python3 scripts/conv1_bench.py [--shapes ring|rule|W,B,g ...]

Forms: ``grouped`` (one group a worker: XLA:TPU's depthwise emitter on
the vector unit), ``packed`` (four workers a group on the MXU, pixels
kept float32: what ships), ``packed_bf16`` (the same with no
``precision=``: the ambient default rounds the pixels to bfloat16),
``padded`` (``packed`` with the batch zero-padded to whole lane tiles and
cut back behind the pool).  Each is a ``lax.scan`` of n dependent
iterations, best of three, in ms a step; ``g`` = forward and gradient,
else forward only.  One JSON line a shape, the table in
``chiprun_out/conv1_bench.json``.  The crossover that ``_conv1_stacked``'s
rule cites (PERF.md §6, PR 31) is the ``rule`` sweep: re-run it when XLA
changes.  Not part of the benchmark (``benchmark/`` never imports it).
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from dopt.models import zoo

FORMS = ("grouped", "packed", "packed_bf16", "padded")
C_OUT, P = 32, 4

SHAPES = {
    # the four shapes the benchmark's two Model1 cells run
    "ring": [(160, 128, True), (160, 128, False), (160, 37, False),
             (128, 256, False), (8, 50, True), (8, 50, False)],
    # the sweep behind the rule (``_CONV1_PACK_WIDTH`` and its comment):
    # widths at batches that are and are not whole lane tiles
    "rule": [(w, b, True) for w in (4, 8, 16, 32, 64, 128, 160)
             for b in (50, 100, 128)]
            + [(w, b, False) for w in (128, 160) for b in (37, 50, 100)],
}


def forward(form, k5, bias, x):
    """[W, 5, 5, 1, 32] kernels, [W, 32] biases, [W, B, 28, 28, 1] pixels →
    pooled [B, 14, 14, W·32]."""
    w, b = x.shape[0], x.shape[1]
    g = zoo._to_grouped_kernel(k5)
    z = jnp.moveaxis(x, 0, 3)
    z = z.reshape(*z.shape[:3], -1)
    if form == "grouped":
        return zoo._max_pool_2x2(
            zoo._conv_fast(z, g, w, dtype=jnp.float32, bias=bias))
    pad = (-b) % 128 if form == "padded" else 0
    if pad:
        z = jnp.pad(z, ((0, pad), (0, 0), (0, 0), (0, 0)))
    precision = None if form == "packed_bf16" else (
        jax.lax.Precision.HIGHEST, jax.lax.Precision.DEFAULT)
    out = zoo._conv_fast(z, zoo._pack_kernel(g, C_OUT, P), w // P,
                         dtype=jnp.float32, bias=bias, precision=precision)
    return zoo._max_pool_2x2(out)[:b]


def make(form, grad):
    def run(k5, bias, x, cot, n):
        def body(carry, _):
            k5, bias = carry
            if grad:
                gk, gb = jax.grad(
                    lambda k, c: jnp.sum(forward(form, k, c, x) * cot),
                    argnums=(0, 1))(k5, bias)
                return (k5 - 1e-6 * gk, bias - 1e-6 * gb), None
            s = forward(form, k5, bias, x)[0, 0, 0, 0] * 1e-9
            return (k5 * (1 + s), bias), None
        return jax.lax.scan(body, (k5, bias), None, length=n)[0]
    return jax.jit(run, static_argnums=4)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", nargs="+", default=["ring"],
                    help="ring, rule, or W,B[,g] triples")
    ap.add_argument("--forms", nargs="+", default=list(FORMS), choices=FORMS)
    ap.add_argument("--out", default="chiprun_out/conv1_bench.json")
    args = ap.parse_args(argv)
    shapes = []
    for s in args.shapes:
        if s in SHAPES:
            shapes += SHAPES[s]
        else:
            w, b, *g = s.split(",")
            shapes.append((int(w), int(b), bool(g)))
    dev = jax.devices()[0]
    print("device", dev.platform, dev.device_kind, flush=True)
    rows = []
    for w, b, grad in shapes:
        key = jax.random.PRNGKey(w * 1000 + b)
        k5 = jax.random.normal(key, (w, 5, 5, 1, C_OUT)) * 0.1
        bias = jnp.zeros((w, C_OUT))
        x = jax.random.normal(jax.random.fold_in(key, 1), (w, b, 28, 28, 1))
        cot = jax.random.normal(jax.random.fold_in(key, 2),
                                (b, 14, 14, w * C_OUT))
        n = 12 if w * b > 5000 else (60 if w * b > 1500 else 200)
        row = {"W": w, "B": b, "grad": grad, "n": n}
        for form in args.forms:
            if form != "grouped" and w % P:
                continue
            f = make(form, grad)
            jax.block_until_ready(f(k5, bias, x, cot, n))
            ts = []
            for _ in range(3):
                t0 = time.perf_counter()
                jax.block_until_ready(f(k5, bias, x, cot, n))
                ts.append((time.perf_counter() - t0) / n * 1e3)
            row[form + "_ms"] = min(ts)
        print(json.dumps(row), flush=True)
        rows.append(row)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as fh:
        json.dump(rows, fh, indent=1)


if __name__ == "__main__":
    main()
