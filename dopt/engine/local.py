"""The local-training step: per-worker SGD epochs as a ``lax.scan``.

This is the reference's inner hot loop (``Client.update_weights``,
``Decentralized Optimization/src/clients.py:36-53`` /
``Client.local_update``, ``Distributed Optimization/src/clients.py:34-59``)
turned into a pure function: given a worker's params + momentum and a
[S, B, ...] batch stack (S = local_ep × steps_per_epoch from the batch
plan), scan SGD steps and return the new state plus per-step metrics.

``make_local_update`` builds the per-worker function; ``vmap`` over the
leading worker axis turns it into the stacked-engine step.  FedProx and
FedADMM enter as gradient edits (``dopt.optim``), with the global model
``theta`` broadcast (in_axes=None) and the ADMM duals stacked per
worker — the dual variables are worker-sharded pytrees, exactly the
TPU mapping SURVEY §2.3 calls for.
"""

from __future__ import annotations

import os
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from dopt.models.losses import (accuracy, accuracy_stacked, cross_entropy,
                                cross_entropy_stacked, l2_regulariser,
                                l2_stacked)
from dopt.optim import (SGDState, admm_grad_edit, clip_by_global_norm,
                        clip_by_global_norm_stacked, prox_grad_edit,
                        scaffold_grad_edit, sgd_step)

# Unroll factor for the inner SGD-step scans: each lax.while iteration
# carries fixed loop bookkeeping (measured ~7% of headline device time
# as `while` self-time); unrolling amortises it over k steps at the
# price of a k-times-larger loop body to compile.  Exposed as an env
# knob for benchmarking; 1 = plain scan.
_SCAN_UNROLL = int(os.environ.get("DOPT_SCAN_UNROLL", "1"))


def validate_optimizer(cfg) -> None:
    """Only 'sgd' exists (the reference's single optimizer,
    clients.py:14); anything else fails loudly at trainer construction
    rather than silently running SGD."""
    if cfg.optim.optimizer.lower() != "sgd":
        raise ValueError(
            f"unknown optimizer {cfg.optim.optimizer!r}: only 'sgd' "
            "exists (the reference's single optimizer, clients.py:14)")


def prepare_holdout(cfg, index_matrix, mesh, *, batch_size):
    """Shared trainer setup for the reference's local train/val holdout
    (``train_val_test`` — P1 clients.py:16-34 / P2 clients.py:19-32).

    Returns ``(use_holdout, train_matrix, (vidx_dev, vw_dev))``: the
    training index matrix (the full shard when the holdout is off) and
    per-worker local-val eval stacks placed with the worker axis sharded.
    When off, the val stacks are [W, 1, 1] zero dummies so jitted round
    signatures stay static either way — both engines rely on that
    contract."""
    import numpy as np

    from dopt.data import holdout_split, stacked_eval_batches
    from dopt.parallel.mesh import worker_sharding

    w = index_matrix.shape[0]
    use = cfg.data.local_holdout > 0.0
    if use:
        train_matrix, val_matrix = holdout_split(
            index_matrix, fraction=cfg.data.local_holdout,
            mode=cfg.data.holdout_mode, seed=cfg.seed)
        vi, vw = stacked_eval_batches(val_matrix, batch_size=batch_size)
    else:
        train_matrix = index_matrix
        vi = np.zeros((w, 1, 1), np.int32)
        vw = np.zeros((w, 1, 1), np.float32)
    sh = worker_sharding(mesh)
    return use, train_matrix, (jax.device_put(vi, sh), jax.device_put(vw, sh))


def _apply_update(p, m, g, *, lr, momentum, update_impl):
    """Dispatch the momentum-SGD update: 'jnp' (tree.map two-liner) or
    'pallas' (fused single-pass kernel, dopt.ops.fused_update).

    The ``dopt_update`` named scope tags the update's HLO ops so the
    profiler can attribute the round's device time into conv / mixing-
    comm / update fractions (``dopt.utils.profiling.classify_phase``,
    surfaced in bench.py's JSON line) — metadata only, numerics and
    compiled programs are unchanged."""
    with jax.named_scope("dopt_update"):
        if update_impl == "pallas":
            from dopt.ops import fused_sgd_momentum_tree

            return fused_sgd_momentum_tree(p, m, g, lr=lr, mu=momentum)
        p, st = sgd_step(p, SGDState(m), g, lr=lr, momentum=momentum)
        return p, st.momentum


class Objective(NamedTuple):
    """What a worker trains: ``(params, batch) -> loss``.

    ``loss(params, x, y, w) -> (scalar, aux)`` is differentiated by the
    step cores and called by the evaluators; ``metric(aux, y, w)`` is the
    step's accuracy, or a dict with ``"acc"`` and the model's own
    per-step counts beside it (the gossip engine averages those into the
    round's history row).  ``w`` is the batch plan's 0/1 row weight."""

    loss: Callable
    metric: Callable


def classification_objective(apply_fn) -> Objective:
    """Cross-entropy of a flax ``apply``'s output against one label a
    row: ``aux`` is the output, the metric its argmax accuracy."""

    def loss(p, x, y, w):
        out = apply_fn({"params": p}, x)
        return cross_entropy(out, y, w), out

    return Objective(loss, accuracy)


def model_objective(model, sample_shape) -> Objective:
    """The training objective of a zoo model over FLAT resident rows: a
    sequence model brings its own ``loss(params, tokens, labels,
    weights) -> (loss, {"acc": ..., **counts})`` over ``[B, T]`` id rows
    (the token contract: the row weight covers every position, negative
    labels are left out); every other model is a classifier of rows
    reshaped to ``sample_shape``."""
    if hasattr(model, "loss"):
        return Objective(model.loss, lambda aux, y, w: aux)
    return classification_objective(
        flat_input_apply(model.apply, sample_shape))


def _as_objective(fn) -> Objective:
    return fn if isinstance(fn, Objective) else classification_objective(fn)


def _acc(metric):
    return metric["acc"] if isinstance(metric, dict) else metric


def _make_step_core(apply_fn, *, lr, momentum, algorithm, rho, l2,
                    update_impl, clip_norm=0.0):
    """One SGD step on concrete batch arrays — the shared body of both
    local-update variants (materialised batches and on-device gather).
    ``apply_fn`` is an ``Objective``, or a flax ``apply`` (a
    classifier)."""
    objective = _as_objective(apply_fn)

    def step_core(p, m, x, y, w, theta=None, alpha=None):
        def loss_fn(p_):
            loss, out = objective.loss(p_, x, y, w)
            if l2:
                loss = loss + l2_regulariser(p_, l2)
            return loss, out

        (loss, out), g = jax.value_and_grad(loss_fn, has_aux=True)(p)
        if algorithm == "fedprox":
            g = prox_grad_edit(g, p, theta, rho)
        elif algorithm == "fedadmm":
            g = admm_grad_edit(g, p, theta, alpha, rho)
        elif algorithm == "scaffold":
            # theta slot carries the server control variate c (broadcast),
            # alpha slot the client control variate c_i (worker-stacked).
            g = scaffold_grad_edit(g, theta, alpha)
        if clip_norm:
            g = clip_by_global_norm(g, clip_norm)
        p, m = _apply_update(p, m, g, lr=lr, momentum=momentum,
                             update_impl=update_impl)
        return p, m, loss, objective.metric(out, y, w)

    return step_core


def _make_stacked_step_core(stacked_apply, *, lr, momentum, algorithm, rho,
                            l2, update_impl, clip_norm=0.0):
    """One SGD step on the FULL [W, B, ...] stacked batch without vmap —
    the grouped-conv fast path (``dopt.models.make_stacked_apply``).

    Gradient identity with the vmapped core: workers are independent, so
    ∂(Σ_w loss_w)/∂p_w = ∂loss_w/∂p_w — differentiating the summed loss
    over the stacked pytree yields exactly each worker's own gradient.
    The per-worker grad edits broadcast naturally (theta leaves [...] vs
    stacked leaves [W, ...]).  Returns per-worker [W] loss/acc rows like
    one vmapped step.
    """

    def step_core(p, m, x, y, w, theta=None, alpha=None):
        def loss_fn(p_):
            out = stacked_apply(p_, x)
            lw = cross_entropy_stacked(out, y, w)
            if l2:
                lw = lw + l2_stacked(p_, l2)
            return lw.sum(), (out, lw)

        (_, (out, lw)), g = jax.value_and_grad(loss_fn, has_aux=True)(p)
        if algorithm == "fedprox":
            g = prox_grad_edit(g, p, theta, rho)
        elif algorithm == "fedadmm":
            g = admm_grad_edit(g, p, theta, alpha, rho)
        elif algorithm == "scaffold":
            g = scaffold_grad_edit(g, theta, alpha)
        if clip_norm:
            g = clip_by_global_norm_stacked(g, clip_norm)
        p, m = _apply_update(p, m, g, lr=lr, momentum=momentum,
                             update_impl=update_impl)
        return p, m, lw, accuracy_stacked(out, y, w)

    return step_core


def _gate_tree(gate, new, old):
    """``new`` where ``gate`` else ``old``, per leaf.  ``gate`` is a
    scalar bool (per-worker vmapped cores) or a [W] bool vector (stacked
    cores), broadcast over each leaf's trailing dims.  The straggler
    deadline model (``dopt.faults``) uses this to freeze a worker's
    params/momentum once its per-round work budget is spent — static
    shapes, no dynamic slicing, dead-cheap when every gate is on."""
    def sel(a, b):
        g = gate
        if getattr(g, "ndim", 0):
            g = g.reshape(g.shape + (1,) * (a.ndim - g.ndim))
        return jnp.where(g, a, b)

    return jax.tree.map(sel, new, old)


def make_local_update(
    apply_fn: Callable,
    *,
    lr: float,
    momentum: float,
    algorithm: str = "sgd",
    rho: float = 0.0,
    l2: float = 0.0,
    update_impl: str = "jnp",
    clip_norm: float = 0.0,
    with_limit: bool = False,
):
    """Build the per-worker local-update function.

    algorithm: 'sgd' (FedAvg / D-SGD local step), 'fedprox', 'fedadmm',
    'scaffold' (theta slot = server control c, alpha slot = client c_i).
    Returns fn(params, mom, bx, by, bw, theta=None, alpha=None) ->
    (new_params, new_mom, losses[S], accs[S]).

    ``with_limit=True`` builds the straggler-deadline variant instead:
    fn(params, mom, bx, by, bw, limit, theta=None, alpha=None) where
    ``limit`` is this worker's SGD-step budget — steps i >= limit leave
    params/momentum frozen (per-step metrics are still emitted; rows
    past the limit reflect the frozen params).  ``limit = S`` is
    bit-identical to the unlimited variant.
    """
    if algorithm not in ("sgd", "fedprox", "fedadmm", "scaffold"):
        raise ValueError(f"unknown local algorithm {algorithm!r}")
    core = _make_step_core(apply_fn, lr=lr, momentum=momentum,
                           algorithm=algorithm, rho=rho, l2=l2,
                           update_impl=update_impl, clip_norm=clip_norm)

    if with_limit:
        def local_update_lim(params, mom, bx, by, bw, limit,
                             theta=None, alpha=None):
            steps = jnp.arange(bx.shape[0])

            def step(carry, batch):
                p, m = carry
                x, y, w, i = batch
                p2, m2, loss, acc = core(p, m, x, y, w, theta, alpha)
                g = i < limit
                return (_gate_tree(g, p2, p), _gate_tree(g, m2, m)), (loss, acc)

            (params, mom), (losses, accs) = jax.lax.scan(
                step, (params, mom), (bx, by, bw, steps))
            return params, mom, losses, accs

        return local_update_lim

    def local_update(params, mom, bx, by, bw, theta=None, alpha=None):
        def step(carry, batch):
            p, m = carry
            x, y, w = batch
            p, m, loss, acc = core(p, m, x, y, w, theta, alpha)
            return (p, m), (loss, acc)

        (params, mom), (losses, accs) = jax.lax.scan(step, (params, mom), (bx, by, bw))
        return params, mom, losses, accs

    return local_update


def _arity_wrap(algorithm, fn):
    """Give the grouped-stacked update the same per-algorithm call arity
    as its vmapped twin (callers pass theta/alpha positionally)."""
    if algorithm == "sgd":
        return lambda *a: fn(*a)
    if algorithm == "fedprox":
        return lambda *a: fn(*a[:-1], theta=a[-1])
    return lambda *a: fn(*a[:-2], theta=a[-2], alpha=a[-1])


def make_stacked_local_update(apply_fn, *, lr, momentum, algorithm="sgd",
                              rho=0.0, l2=0.0, update_impl="jnp",
                              stacked_apply=None, clip_norm=0.0,
                              with_limit=False):
    """vmap the per-worker update over the leading worker axis — or,
    with ``stacked_apply`` set (``dopt.models.make_stacked_apply``), run
    the grouped-conv stacked step with NO vmap: the scan iterates over
    S-major batches and every step consumes the full [W, B, ...] slab.

    theta (global model) is broadcast; alpha (ADMM duals) is stacked.
    ``with_limit=True`` builds the straggler-deadline variant: a [W]
    int ``limit`` rides after ``bw`` and worker w's params/momentum
    freeze from step limit[w] on (``make_local_update``).
    """
    if stacked_apply is not None:
        core = _make_stacked_step_core(
            stacked_apply, lr=lr, momentum=momentum, algorithm=algorithm,
            rho=rho, l2=l2, update_impl=update_impl, clip_norm=clip_norm)

        if with_limit:
            def fn_lim(p, m, bx, by, bw, limit, theta=None, alpha=None):
                xs = (bx.swapaxes(0, 1), by.swapaxes(0, 1),
                      bw.swapaxes(0, 1), jnp.arange(bx.shape[1]))

                def step(carry, batch):
                    p_, m_ = carry
                    x, y, w, i = batch
                    p2, m2, lw, aw = core(p_, m_, x, y, w, theta, alpha)
                    g = i < limit
                    return (_gate_tree(g, p2, p_),
                            _gate_tree(g, m2, m_)), (lw, aw)

                (p, m), (losses, accs) = jax.lax.scan(step, (p, m), xs)
                return p, m, losses.swapaxes(0, 1), accs.swapaxes(0, 1)

            return _arity_wrap(algorithm, fn_lim)

        def fn(p, m, bx, by, bw, theta=None, alpha=None):
            xs = (bx.swapaxes(0, 1), by.swapaxes(0, 1), bw.swapaxes(0, 1))

            def step(carry, batch):
                p_, m_ = carry
                x, y, w = batch
                p_, m_, lw, aw = core(p_, m_, x, y, w, theta, alpha)
                return (p_, m_), (lw, aw)

            (p, m), (losses, accs) = jax.lax.scan(step, (p, m), xs)
            return p, m, losses.swapaxes(0, 1), accs.swapaxes(0, 1)

        return _arity_wrap(algorithm, fn)
    fn = make_local_update(apply_fn, lr=lr, momentum=momentum,
                           algorithm=algorithm, rho=rho, l2=l2,
                           update_impl=update_impl, clip_norm=clip_norm,
                           with_limit=with_limit)
    if with_limit:
        if algorithm == "sgd":
            return jax.vmap(
                lambda p, m, bx, by, bw, lim: fn(p, m, bx, by, bw, lim))
        if algorithm == "fedprox":
            return jax.vmap(
                lambda p, m, bx, by, bw, lim, theta: fn(
                    p, m, bx, by, bw, lim, theta=theta),
                in_axes=(0, 0, 0, 0, 0, 0, None),
            )
        return jax.vmap(
            lambda p, m, bx, by, bw, lim, theta, alpha: fn(
                p, m, bx, by, bw, lim, theta=theta, alpha=alpha),
            in_axes=(0, 0, 0, 0, 0, 0, None, 0),
        )
    if algorithm == "sgd":
        return jax.vmap(lambda p, m, bx, by, bw: fn(p, m, bx, by, bw))
    if algorithm == "fedprox":
        return jax.vmap(
            lambda p, m, bx, by, bw, theta: fn(p, m, bx, by, bw, theta=theta),
            in_axes=(0, 0, 0, 0, 0, None),
        )
    return jax.vmap(
        lambda p, m, bx, by, bw, theta, alpha: fn(p, m, bx, by, bw,
                                                  theta=theta, alpha=alpha),
        in_axes=(0, 0, 0, 0, 0, None, 0),
    )


def flat_input_apply(apply_fn, sample_shape):
    """Wrap a flax ``apply`` so it accepts FLAT feature rows and
    reshapes them to the model's input shape at use.

    The engines keep the resident train arrays flat ([N, F] instead of
    [N, H, W, C]) because TPU row-gathers from an [N, 28, 28, 1] array
    run ~2.6× slower end-to-end than from [N, 784] and the C=1-minor
    layout additionally poisons the layouts of everything computed from
    the gathered slab (measured on v5e: 1.42 → 0.55 ms/step on the
    headline workload).  A no-op when the rows are already shaped.
    """
    def wrapped(variables, x):
        return apply_fn(variables, x.reshape(x.shape[0], *sample_shape))

    return wrapped


def flat_input_stacked_apply(stacked_apply, sample_shape):
    """``flat_input_apply`` for the grouped stacked forward
    ([W, B, F] flat rows → [W, B, *sample_shape])."""
    def wrapped(params, x):
        return stacked_apply(params, x.reshape(*x.shape[:2], *sample_shape))

    return wrapped


def gather_rows(train_x, train_y, idx):
    """The on-device gather of the rows ``idx`` names (a step's batch, a
    chunk's slab, a round's plan, a holdout) from the resident train
    arrays, under the ``dopt_batch`` scope."""
    with jax.named_scope("dopt_batch"):
        return train_x[idx], train_y[idx]


def pick_gather_chunks(steps: int, *, workers: int, batch: int,
                       sample_bytes: int,
                       budget_bytes: int = 256 * 1024 * 1024) -> int | None:
    """Choose how many chunks to split a [S, B] plan into so each chunk's
    materialised batch slab ([W, S/k, B, sample]) fits ``budget_bytes``.

    Rationale: gathering one minibatch per step inside the scan costs
    ~250 µs of fixed gather overhead per step on a v5e (18% of device
    time on the headline workload, results/trace_headline.json); one big
    gather per chunk runs at memcpy speed.  Returns the smallest divisor
    of ``steps`` whose slab fits, or None (meaning: keep the per-step
    gather) when even per-step slabs would blow the budget — which
    cannot happen in practice since k=steps is always a divisor.
    """
    for k in range(1, steps + 1):
        if steps % k:
            continue
        if workers * (steps // k) * batch * sample_bytes <= budget_bytes:
            return k
    return None


def _scan_steps_gathered(core, params, mom, idx, bw, train_x, train_y,
                         theta, alpha, gather_chunks, limit=None):
    """Scan SGD steps over a [S, B] index plan against the resident train
    arrays.  ``gather_chunks=None`` gathers each minibatch inside the
    step body (O(B·|x|) live memory, one small gather per step);
    ``gather_chunks=k`` splits S into k chunks and materialises each
    chunk's batches with ONE big gather (O((S/k)·B·|x|) live memory) —
    same indices, same order, bit-identical numerics, far less per-step
    gather overhead.  ``limit`` (straggler deadline) carries a step
    counter and freezes params/momentum from step ``limit`` on."""

    gated = limit is not None

    def step(carry, batch):
        if gated:
            p, m, k = carry
            x, y, w = batch
            p2, m2, loss, acc = core(p, m, x, y, w, theta, alpha)
            g = k < limit
            return (_gate_tree(g, p2, p), _gate_tree(g, m2, m),
                    k + 1), (loss, acc)
        p, m = carry
        x, y, w = batch
        p, m, loss, acc = core(p, m, x, y, w, theta, alpha)
        return (p, m), (loss, acc)

    carry0 = ((params, mom, jnp.zeros((), jnp.int32)) if gated
              else (params, mom))

    def strip(carry):
        return carry[:2] if gated else carry

    if gather_chunks is None:
        def gstep(carry, batch):
            i, w = batch
            return step(carry, (*gather_rows(train_x, train_y, i), w))

        carry, out = jax.lax.scan(gstep, carry0, (idx, bw))
        return strip(carry), out

    s, b = idx.shape
    if s % gather_chunks:
        raise ValueError(
            f"gather_chunks={gather_chunks} does not divide steps={s}")
    idx_c = idx.reshape(gather_chunks, s // gather_chunks, b)
    bw_c = bw.reshape(gather_chunks, s // gather_chunks, b)

    def chunk(carry, ch):
        ci, cw = ch
        return jax.lax.scan(step, carry,
                            (*gather_rows(train_x, train_y, ci), cw))

    carry, (losses, accs) = jax.lax.scan(chunk, carry0, (idx_c, bw_c))
    # (a sequence model's step metric is a dict of [S] counts)
    return strip(carry), (losses.reshape(s),
                          jax.tree.map(lambda a: a.reshape(s), accs))


def make_local_update_gather(
    apply_fn: Callable,
    *,
    lr: float,
    momentum: float,
    algorithm: str = "sgd",
    rho: float = 0.0,
    l2: float = 0.0,
    update_impl: str = "jnp",
    gather_chunks: int | None = None,
    clip_norm: float = 0.0,
    with_limit: bool = False,
):
    """Like ``make_local_update`` but gathers minibatches from the full
    on-device dataset inside the scan: the caller passes the [S, B]
    index/weight plan plus the resident train arrays instead of
    materialised [S, B, ...] batches.  Peak activation memory drops from
    O(S·B·|x|) to O((S/k)·B·|x|) (k = ``gather_chunks``; None = one
    small gather per step, O(B·|x|)), which is what lets the fused
    multi-round block path keep K rounds of plans on device at once.

    Returns fn(params, mom, idx, bw, train_x, train_y, theta=None,
    alpha=None) -> (new_params, new_mom, losses[S], accs[S]); with
    ``with_limit=True`` the straggler step budget rides after ``bw``:
    fn(params, mom, idx, bw, limit, train_x, train_y, ...).
    """
    if algorithm not in ("sgd", "fedprox", "fedadmm", "scaffold"):
        raise ValueError(f"unknown local algorithm {algorithm!r}")
    core = _make_step_core(apply_fn, lr=lr, momentum=momentum,
                           algorithm=algorithm, rho=rho, l2=l2,
                           update_impl=update_impl, clip_norm=clip_norm)

    if with_limit:
        def local_update_lim(params, mom, idx, bw, limit, train_x, train_y,
                             theta=None, alpha=None):
            (params, mom), (losses, accs) = _scan_steps_gathered(
                core, params, mom, idx, bw, train_x, train_y, theta, alpha,
                gather_chunks, limit=limit)
            return params, mom, losses, accs

        return local_update_lim

    def local_update(params, mom, idx, bw, train_x, train_y,
                     theta=None, alpha=None):
        (params, mom), (losses, accs) = _scan_steps_gathered(
            core, params, mom, idx, bw, train_x, train_y, theta, alpha,
            gather_chunks)
        return params, mom, losses, accs

    return local_update


def _scan_steps_gathered_stacked(core, params, mom, idx, bw, train_x,
                                 train_y, theta, alpha, gather_chunks,
                                 limit=None):
    """Stacked-core twin of ``_scan_steps_gathered``: ``idx``/``bw`` are
    [W, S, B]; the scan runs S-major and each step consumes the full
    [W, B, ...] slab.  Returns per-worker [W, S] loss/acc grids.
    ``limit`` ([W] ints, straggler deadline) freezes worker w's lanes
    from step limit[w] on."""
    idx_s = idx.swapaxes(0, 1)   # [S, W, B]
    bw_s = bw.swapaxes(0, 1)
    gated = limit is not None

    def step(carry, batch):
        if gated:
            p, m, k = carry
            x, y, w = batch
            p2, m2, lw, aw = core(p, m, x, y, w, theta, alpha)
            g = k < limit      # [W] bool
            return (_gate_tree(g, p2, p), _gate_tree(g, m2, m),
                    k + 1), (lw, aw)
        p, m = carry
        x, y, w = batch
        p, m, lw, aw = core(p, m, x, y, w, theta, alpha)
        return (p, m), (lw, aw)

    carry0 = ((params, mom, jnp.zeros((), jnp.int32)) if gated
              else (params, mom))

    def strip(carry):
        return carry[:2] if gated else carry

    if gather_chunks is None:
        def gstep(carry, batch):
            i, w = batch
            return step(carry, (*gather_rows(train_x, train_y, i), w))

        carry, (losses, accs) = jax.lax.scan(gstep, carry0,
                                             (idx_s, bw_s),
                                             unroll=_SCAN_UNROLL)
        return strip(carry), (losses.swapaxes(0, 1), accs.swapaxes(0, 1))

    s = idx_s.shape[0]
    if s % gather_chunks:
        raise ValueError(
            f"gather_chunks={gather_chunks} does not divide steps={s}")
    idx_c = idx_s.reshape(gather_chunks, s // gather_chunks, *idx_s.shape[1:])
    bw_c = bw_s.reshape(idx_c.shape)

    def chunk(carry, ch):
        ci, cw = ch
        return jax.lax.scan(step, carry,
                            (*gather_rows(train_x, train_y, ci), cw),
                            unroll=_SCAN_UNROLL)

    carry, (losses, accs) = jax.lax.scan(chunk, carry0, (idx_c, bw_c))
    w_ = idx.shape[0]
    return strip(carry), (losses.reshape(s, w_).swapaxes(0, 1),
                          accs.reshape(s, w_).swapaxes(0, 1))


def make_stacked_local_update_gather(apply_fn, *, lr, momentum,
                                     algorithm="sgd", rho=0.0, l2=0.0,
                                     update_impl="jnp",
                                     gather_chunks=None,
                                     stacked_apply=None, clip_norm=0.0,
                                     with_limit=False):
    """vmap the gather-variant over the leading worker axis; train arrays
    and theta broadcast, ADMM duals stacked per worker.  With
    ``stacked_apply`` set, the grouped-conv stacked path replaces the
    vmap (see ``make_stacked_local_update``).  ``with_limit=True``: a
    [W] straggler step budget rides after ``bw``."""
    if stacked_apply is not None:
        core = _make_stacked_step_core(
            stacked_apply, lr=lr, momentum=momentum, algorithm=algorithm,
            rho=rho, l2=l2, update_impl=update_impl, clip_norm=clip_norm)

        if with_limit:
            def fn_lim(p, m, idx, bw, limit, tx, ty, theta=None, alpha=None):
                (p, m), (losses, accs) = _scan_steps_gathered_stacked(
                    core, p, m, idx, bw, tx, ty, theta, alpha,
                    gather_chunks, limit=limit)
                return p, m, losses, accs

            return _arity_wrap(algorithm, fn_lim)

        def fn(p, m, idx, bw, tx, ty, theta=None, alpha=None):
            (p, m), (losses, accs) = _scan_steps_gathered_stacked(
                core, p, m, idx, bw, tx, ty, theta, alpha, gather_chunks)
            return p, m, losses, accs

        return _arity_wrap(algorithm, fn)
    fn = make_local_update_gather(apply_fn, lr=lr, momentum=momentum,
                                  algorithm=algorithm, rho=rho, l2=l2,
                                  update_impl=update_impl,
                                  gather_chunks=gather_chunks,
                                  clip_norm=clip_norm,
                                  with_limit=with_limit)
    if with_limit:
        if algorithm == "sgd":
            return jax.vmap(
                lambda p, m, idx, bw, lim, tx, ty: fn(
                    p, m, idx, bw, lim, tx, ty),
                in_axes=(0, 0, 0, 0, 0, None, None),
            )
        if algorithm == "fedprox":
            return jax.vmap(
                lambda p, m, idx, bw, lim, tx, ty, theta: fn(
                    p, m, idx, bw, lim, tx, ty, theta=theta),
                in_axes=(0, 0, 0, 0, 0, None, None, None),
            )
        return jax.vmap(
            lambda p, m, idx, bw, lim, tx, ty, theta, alpha: fn(
                p, m, idx, bw, lim, tx, ty, theta=theta, alpha=alpha),
            in_axes=(0, 0, 0, 0, 0, None, None, None, 0),
        )
    if algorithm == "sgd":
        return jax.vmap(
            lambda p, m, idx, bw, tx, ty: fn(p, m, idx, bw, tx, ty),
            in_axes=(0, 0, 0, 0, None, None),
        )
    if algorithm == "fedprox":
        return jax.vmap(
            lambda p, m, idx, bw, tx, ty, theta: fn(p, m, idx, bw, tx, ty,
                                                    theta=theta),
            in_axes=(0, 0, 0, 0, None, None, None),
        )
    return jax.vmap(
        lambda p, m, idx, bw, tx, ty, theta, alpha: fn(
            p, m, idx, bw, tx, ty, theta=theta, alpha=alpha),
        in_axes=(0, 0, 0, 0, None, None, None, 0),
    )


def make_local_update_epochs(
    apply_fn: Callable,
    *,
    lr: float,
    momentum: float,
    algorithm: str = "sgd",
    rho: float = 0.0,
    l2: float = 0.0,
    update_impl: str = "jnp",
    gather_chunks: int | None = None,
    clip_norm: float = 0.0,
    with_limit: bool = False,
):
    """Local update with the reference's EPOCH structure: an outer scan
    over local epochs, each running its steps then evaluating the
    worker's local validation holdout — ``Client.update_weights``'s
    per-epoch ``inference`` + history row
    (``Decentralized Optimization/src/clients.py:38-50`` /
    ``Distributed Optimization/src/clients.py:37-57``).

    Returns fn(params, mom, idx, bw, train_x, train_y, vidx, vw,
    theta=None, alpha=None) -> (new_params, new_mom, em) where ``idx``/
    ``bw`` are [E, S', B] epoch-major plans, ``vidx``/``vw`` the [Sv, Bv]
    local-val eval stacks, and ``em`` maps per-epoch [E] arrays:

    * train_loss — mean over the epoch's batches of the batch-mean loss
      (``sum(train_loss)/len(train_loss)``, clients.py:47)
    * train_acc  — epoch correct count / train-set size
      (``train_acc += corr/total``, clients.py:44-45)
    * val_acc / val_loss_sum / val_loss_mean — post-epoch local-val
      metrics in both reference flavours (P1 ``inference`` sums batch
      losses, P2 averages them).

    ``with_limit=True`` builds the straggler-deadline variant: an EPOCH
    budget rides after ``bw`` — fn(params, mom, idx, bw, limit,
    train_x, train_y, vidx, vw, ...) — and epochs e >= limit leave
    params/momentum frozen (their em rows then reflect the frozen
    params: the straggler's val metrics stop moving at its deadline).
    """
    if algorithm not in ("sgd", "fedprox", "fedadmm", "scaffold"):
        raise ValueError(f"unknown local algorithm {algorithm!r}")
    core = _make_step_core(apply_fn, lr=lr, momentum=momentum,
                           algorithm=algorithm, rho=rho, l2=l2,
                           update_impl=update_impl, clip_norm=clip_norm)
    ev = make_evaluator(apply_fn)

    def _epoch_steps(p, m, ei, ew, train_x, train_y, theta, alpha):
        """One epoch's SGD steps: returns ((p, m), (losses, corrects,
        counts)) — shared by the unlimited and straggler-gated variants
        so their inner numerics can never diverge."""

        def step(c, b):
            p_, m_ = c
            i, w_ = b
            p_, m_, loss, acc = core(p_, m_, *gather_rows(train_x, train_y, i),
                                     w_, theta, alpha)
            return (p_, m_), (loss, acc * w_.sum(), w_.sum())

        def stepm(c, b):
            p_, m_ = c
            x, y, w_ = b
            p_, m_, loss, acc = core(p_, m_, x, y, w_, theta, alpha)
            return (p_, m_), (loss, acc * w_.sum(), w_.sum())

        if gather_chunks is None:
            return jax.lax.scan(step, (p, m), (ei, ew))
        # Chunked big-gather within the epoch: same indices, same
        # order, one slab gather per chunk instead of one small
        # gather per step (see _scan_steps_gathered).
        se, bsz = ei.shape
        if se % gather_chunks:
            raise ValueError(
                f"gather_chunks={gather_chunks} does not divide "
                f"steps/epoch={se}")
        ei_c = ei.reshape(gather_chunks, se // gather_chunks, bsz)
        ew_c = ew.reshape(ei_c.shape)

        def chunk(c, ch):
            ci, cw = ch
            return jax.lax.scan(stepm, c,
                                (*gather_rows(train_x, train_y, ci), cw))

        (p, m), (losses, corrects, counts) = jax.lax.scan(
            chunk, (p, m), (ei_c, ew_c))
        return (p, m), (losses.reshape(se), corrects.reshape(se),
                        counts.reshape(se))

    if with_limit:
        def local_update_lim(params, mom, idx, bw, limit, train_x, train_y,
                             vidx, vw, theta=None, alpha=None):
            # The unlimited epoch body with each epoch's carry gated:
            # identical inner numerics, and the single post-epoch val
            # eval sees the GATED params (a frozen straggler's val
            # metrics reflect its frozen model).
            vx, vy = gather_rows(train_x, train_y, vidx)

            def epoch(carry, ep):
                p, m = carry
                ei, ew, e = ep
                (p2, m2), (losses, corrects, counts) = _epoch_steps(
                    p, m, ei, ew, train_x, train_y, theta, alpha)
                g = e < limit
                p = _gate_tree(g, p2, p)
                m = _gate_tree(g, m2, m)
                # Train metrics for skipped epochs report 0 (the worker
                # did no work — the fault ledger records the truncation).
                vm = ev(p, vx, vy, vw)
                em = {
                    "train_loss": jnp.where(g, losses.mean(), 0.0),
                    "train_acc": jnp.where(
                        g, corrects.sum() / jnp.maximum(counts.sum(), 1.0),
                        0.0),
                    "val_acc": vm["acc"],
                    "val_loss_sum": vm["loss_sum"],
                    "val_loss_mean": vm["loss_mean"],
                }
                return (p, m), em

            (params, mom), em = jax.lax.scan(
                epoch, (params, mom),
                (idx, bw, jnp.arange(idx.shape[0])))
            return params, mom, em

        return local_update_lim

    def local_update(params, mom, idx, bw, train_x, train_y, vidx, vw,
                     theta=None, alpha=None):
        vx, vy = gather_rows(train_x, train_y, vidx)

        def epoch(carry, ep):
            p, m = carry
            ei, ew = ep
            (p, m), (losses, corrects, counts) = _epoch_steps(
                p, m, ei, ew, train_x, train_y, theta, alpha)
            vm = ev(p, vx, vy, vw)
            em = {
                "train_loss": losses.mean(),
                "train_acc": corrects.sum() / jnp.maximum(counts.sum(), 1.0),
                "val_acc": vm["acc"],
                "val_loss_sum": vm["loss_sum"],
                "val_loss_mean": vm["loss_mean"],
            }
            return (p, m), em

        (params, mom), em = jax.lax.scan(epoch, (params, mom), (idx, bw))
        return params, mom, em

    return local_update


def _stacked_eval_scan(stacked_apply, params, ex, ey, ew):
    """Eval a [W, ...]-stacked fleet over S-major [S, W, B, ...] batch
    stacks via the grouped forward; returns per-worker [W] metric dict
    (same fields as ``make_evaluator``)."""

    def step(c, b):
        x, y, w = b
        out = stacked_apply(params, x)
        loss = cross_entropy_stacked(out, y, w)
        corr = accuracy_stacked(out, y, w) * w.sum(axis=-1)
        return c, (loss, corr, w.sum(axis=-1))

    with jax.named_scope("dopt_eval"):
        _, (losses, corrects, counts) = jax.lax.scan(step, (), (ex, ey, ew))
        total = jnp.maximum(counts.sum(axis=0), 1.0)
        return {"acc": corrects.sum(axis=0) / total,
                "loss_sum": losses.sum(axis=0),
                "loss_mean": losses.mean(axis=0), "count": total}


def make_stacked_local_update_epochs(apply_fn, *, lr, momentum,
                                     algorithm="sgd", rho=0.0, l2=0.0,
                                     update_impl="jnp", gather_chunks=None,
                                     stacked_apply=None, clip_norm=0.0,
                                     with_limit=False):
    """vmap the epoch-structured update over the leading worker axis;
    train arrays and theta broadcast, per-worker plans / val stacks /
    ADMM duals stacked.  With ``stacked_apply`` set, the grouped-conv
    stacked path replaces the vmap (see ``make_stacked_local_update``).
    ``with_limit=True``: a [W] straggler EPOCH budget rides after
    ``bw`` (see ``make_local_update_epochs``)."""
    if stacked_apply is not None:
        core = _make_stacked_step_core(
            stacked_apply, lr=lr, momentum=momentum, algorithm=algorithm,
            rho=rho, l2=l2, update_impl=update_impl, clip_norm=clip_norm)

        if with_limit:
            def fn_lim(p, m, idx, bw, elimit, tx, ty, vi, vw_,
                       theta=None, alpha=None):
                vi_s = vi.swapaxes(0, 1)
                vw_s = vw_.swapaxes(0, 1)
                vx, vy = gather_rows(tx, ty, vi_s)
                idx_e = idx.swapaxes(0, 1)
                bw_e = bw.swapaxes(0, 1)

                def epoch(carry, ep):
                    p_, m_ = carry
                    ei, ew, e = ep
                    (p2, m2), (lws, aws) = _scan_steps_gathered_stacked(
                        core, p_, m_, ei, ew, tx, ty, theta, alpha,
                        gather_chunks)
                    g = e < elimit          # [W] bool epoch gate
                    p_ = _gate_tree(g, p2, p_)
                    m_ = _gate_tree(g, m2, m_)
                    counts = ew.sum(axis=-1)
                    vm = _stacked_eval_scan(stacked_apply, p_, vx, vy, vw_s)
                    em = {
                        "train_loss": jnp.where(g, lws.mean(axis=1), 0.0),
                        "train_acc": jnp.where(
                            g, (aws * counts).sum(axis=1)
                            / jnp.maximum(counts.sum(axis=1), 1.0), 0.0),
                        "val_acc": vm["acc"],
                        "val_loss_sum": vm["loss_sum"],
                        "val_loss_mean": vm["loss_mean"],
                    }
                    return (p_, m_), em

                (p, m), em = jax.lax.scan(
                    epoch, (p, m),
                    (idx_e, bw_e, jnp.arange(idx_e.shape[0])))
                em = {k: v.swapaxes(0, 1) for k, v in em.items()}  # [W, E]
                return p, m, em

            return _arity_wrap(algorithm, fn_lim)

        def fn(p, m, idx, bw, tx, ty, vi, vw_, theta=None, alpha=None):
            vi_s = vi.swapaxes(0, 1)        # [Sv, W, Bv]
            vw_s = vw_.swapaxes(0, 1)
            vx, vy = gather_rows(tx, ty, vi_s)
            idx_e = idx.swapaxes(0, 1)      # [E, W, Se, B]
            bw_e = bw.swapaxes(0, 1)

            def epoch(carry, ep):
                p_, m_ = carry
                ei, ew = ep                 # [W, Se, B]
                (p_, m_), (lws, aws) = _scan_steps_gathered_stacked(
                    core, p_, m_, ei, ew, tx, ty, theta, alpha,
                    gather_chunks)
                counts = ew.sum(axis=-1)    # [W, Se]
                vm = _stacked_eval_scan(stacked_apply, p_, vx, vy, vw_s)
                em = {
                    "train_loss": lws.mean(axis=1),
                    "train_acc": ((aws * counts).sum(axis=1)
                                  / jnp.maximum(counts.sum(axis=1), 1.0)),
                    "val_acc": vm["acc"],
                    "val_loss_sum": vm["loss_sum"],
                    "val_loss_mean": vm["loss_mean"],
                }
                return (p_, m_), em

            (p, m), em = jax.lax.scan(epoch, (p, m), (idx_e, bw_e))
            em = {k: v.swapaxes(0, 1) for k, v in em.items()}  # [W, E]
            return p, m, em

        return _arity_wrap(algorithm, fn)
    fn = make_local_update_epochs(apply_fn, lr=lr, momentum=momentum,
                                  algorithm=algorithm, rho=rho, l2=l2,
                                  update_impl=update_impl,
                                  gather_chunks=gather_chunks,
                                  clip_norm=clip_norm,
                                  with_limit=with_limit)
    if with_limit:
        if algorithm == "sgd":
            return jax.vmap(
                lambda p, m, idx, bw, lim, tx, ty, vi, vw_: fn(
                    p, m, idx, bw, lim, tx, ty, vi, vw_),
                in_axes=(0, 0, 0, 0, 0, None, None, 0, 0),
            )
        if algorithm == "fedprox":
            return jax.vmap(
                lambda p, m, idx, bw, lim, tx, ty, vi, vw_, theta: fn(
                    p, m, idx, bw, lim, tx, ty, vi, vw_, theta=theta),
                in_axes=(0, 0, 0, 0, 0, None, None, 0, 0, None),
            )
        return jax.vmap(
            lambda p, m, idx, bw, lim, tx, ty, vi, vw_, theta, alpha: fn(
                p, m, idx, bw, lim, tx, ty, vi, vw_, theta=theta,
                alpha=alpha),
            in_axes=(0, 0, 0, 0, 0, None, None, 0, 0, None, 0),
        )
    if algorithm == "sgd":
        return jax.vmap(
            lambda p, m, idx, bw, tx, ty, vi, vw_: fn(p, m, idx, bw, tx, ty,
                                                      vi, vw_),
            in_axes=(0, 0, 0, 0, None, None, 0, 0),
        )
    if algorithm == "fedprox":
        return jax.vmap(
            lambda p, m, idx, bw, tx, ty, vi, vw_, theta: fn(
                p, m, idx, bw, tx, ty, vi, vw_, theta=theta),
            in_axes=(0, 0, 0, 0, None, None, 0, 0, None),
        )
    return jax.vmap(
        lambda p, m, idx, bw, tx, ty, vi, vw_, theta, alpha: fn(
            p, m, idx, bw, tx, ty, vi, vw_, theta=theta, alpha=alpha),
        in_axes=(0, 0, 0, 0, None, None, 0, 0, None, 0),
    )


def make_evaluator(apply_fn):
    """Batched evaluation over a static [S, B, ...] eval stack.

    Returns fn(params, ex, ey, ew) -> dict with weighted sums so the
    caller can form either reference metric flavour:
    P1 ``inference`` returns (acc, summed-per-batch loss)
    (``Decentralized Optimization/src/clients.py:61-75``), P2 returns
    (acc, mean-per-batch loss) (``Distributed Optimization/src/clients.py:71-86``).
    ``apply_fn`` is an ``Objective``, or a flax ``apply`` (a classifier).
    """
    objective = _as_objective(apply_fn)

    def evaluate(params, ex, ey, ew):
        def step(carry, batch):
            x, y, w = batch
            loss, out = objective.loss(params, x, y, w)  # weighted mean over batch
            correct = _acc(objective.metric(out, y, w)) * w.sum()  # weighted correct count
            return carry, (loss, correct, w.sum())

        with jax.named_scope("dopt_eval"):
            _, (losses, corrects, counts) = jax.lax.scan(
                step, (), (ex, ey, ew))
            total = jnp.maximum(counts.sum(), 1.0)
            return {
                "acc": corrects.sum() / total,
                "loss_sum": losses.sum(),            # P1 flavour (summed batch losses)
                "loss_mean": losses.mean(),          # P2 flavour (mean per batch)
                "count": total,
            }

    return evaluate


def make_stacked_evaluator(apply_fn, stacked_apply=None):
    """Evaluate every worker's params on the same (replicated) eval stack.
    With ``stacked_apply`` set, the grouped forward replaces the vmap
    (each eval batch is broadcast across the worker axis)."""
    if stacked_apply is not None:
        def evaluate(params, ex, ey, ew):
            w_count = jax.tree_util.tree_leaves(params)[0].shape[0]

            def step(c, b):
                x, y, w = b
                xw = jnp.broadcast_to(x[None], (w_count,) + x.shape)
                yw = jnp.broadcast_to(y[None], (w_count,) + y.shape)
                ww = jnp.broadcast_to(w[None], (w_count,) + w.shape)
                out = stacked_apply(params, xw)
                loss = cross_entropy_stacked(out, yw, ww)
                corr = accuracy_stacked(out, yw, ww) * w.sum()
                return c, (loss, corr, w.sum())

            with jax.named_scope("dopt_eval"):
                _, (losses, corrects, counts) = jax.lax.scan(
                    step, (), (ex, ey, ew))
                total = jnp.maximum(counts.sum(), 1.0)
                return {"acc": corrects.sum(axis=0) / total,
                        "loss_sum": losses.sum(axis=0),
                        "loss_mean": losses.mean(axis=0),
                        "count": jnp.full((w_count,), total)}

        return evaluate
    ev = make_evaluator(apply_fn)
    return jax.vmap(lambda p, ex, ey, ew: ev(p, ex, ey, ew),
                    in_axes=(0, None, None, None))
