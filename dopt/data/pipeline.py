"""Host-side batch planning for the stacked-worker TPU engine.

The reference gives each client its own ``DataLoader(shuffle=True)``
(``Decentralized Optimization/src/clients.py:16-34``).  The TPU engine
instead runs ONE program over a ``[workers, ...]`` stacked state, so
batching becomes data: a deterministic per-(round, epoch, worker)
shuffled index tensor, gathered host-side into
``[workers, steps, batch, ...]`` arrays and sharded along the worker
mesh axis (SURVEY §7 hard part: per-worker data feeding one program).

Static shapes for XLA: the last partial batch is padded by wraparound
with a 0/1 sample-weight mask; losses and metrics are mask-weighted so
padding never changes the math.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass(frozen=True)
class BatchPlan:
    """Index plan for one round of local training on every worker.

    idx:    [W, S, B] int32 — S = local_ep * steps_per_epoch gather indices
    weight: [W, S, B] float32 — 1.0 for real samples, 0.0 for padding
    """

    idx: np.ndarray
    weight: np.ndarray

    @property
    def num_workers(self) -> int:
        return self.idx.shape[0]

    @property
    def steps(self) -> int:
        return self.idx.shape[1]

    @property
    def batch_size(self) -> int:
        return self.idx.shape[2]


def make_batch_plan(
    index_matrix: np.ndarray,
    *,
    batch_size: int,
    local_ep: int = 1,
    seed: int = 0,
    round_idx: int = 0,
    drop_last: bool = False,
    impl: str = "numpy",
    workers: np.ndarray | None = None,
    rows: np.ndarray | None = None,
) -> BatchPlan:
    """Build the shuffled batch plan for one round.

    ``index_matrix`` is [W, L] per-worker dataset indices (from
    ``dopt.data.partition``).  Shuffling is deterministic in
    (seed, round_idx, epoch, worker) so the torch oracle and the jax
    engine consume byte-identical batches — that determinism is what
    makes step-level numerics parity testable at all.

    ``workers`` (optional [m] int array of worker ids) plans only those
    workers' rows, returning an [m, S, B] plan bit-identical to the
    matching rows of the full plan — the RNG is keyed by the TRUE worker
    id, not the row position.  This keeps the compact-sampling fast path
    O(m) on the host instead of O(W).

    ``rows`` (optional [m] int array, requires ``workers``) decouples
    the DATA rows gathered from the RNG identities: row ``rows[i]`` of
    ``index_matrix`` is shuffled under worker key ``workers[i]``.  The
    client-population path (``dopt.population``) uses this to bind a
    cohort of clients onto their assigned data shards — two clients
    sharing a shard still draw DISTINCT client-keyed batch streams,
    and when client ids equal shard ids the plan is bit-identical to
    the classic per-worker plan (the cohort-vs-flat parity contract).

    ``impl='native'`` fills the plan with the C++ host runtime
    (``dopt.native``) — same contract and determinism key, different
    (xoshiro) RNG stream, so it is the throughput mode, not the
    oracle-parity mode; raises ``dopt.native.NativeUnavailable`` when
    the library cannot be built (the numpy planner draws a different
    batch order, so it is never substituted).
    """
    worker_ids = None
    if rows is not None and workers is None:
        raise ValueError("make_batch_plan: rows= requires workers= "
                         "(the RNG identity keys)")
    if workers is not None:
        worker_ids = np.asarray(workers, dtype=np.int64)
        sel = (np.asarray(rows, dtype=np.int64) if rows is not None
               else worker_ids)
        index_matrix = index_matrix[sel]
    if impl == "native":
        from dopt.native import fill_batch_plan_native

        idx, weight = fill_batch_plan_native(
            index_matrix, batch_size=batch_size, local_ep=local_ep,
            seed=seed, round_idx=round_idx, drop_last=drop_last,
            worker_ids=worker_ids,
        )
        return BatchPlan(idx=idx, weight=weight)
    w, l = index_matrix.shape
    bs = min(batch_size, l)
    if drop_last:
        steps_per_epoch = l // bs
        padded = steps_per_epoch * bs
    else:
        steps_per_epoch = -(-l // bs)  # ceil
        padded = steps_per_epoch * bs
    s = local_ep * steps_per_epoch

    # Per-(worker, epoch) permutations keep their SeedSequence keys —
    # the (seed, round, ep, wid) keying is the byte-identity contract
    # with the torch oracle and every historical plan — but everything
    # downstream of the draws (wraparound padding, the gather from
    # index_matrix, the [W, S, B] reshape) runs as batched numpy ops
    # over the whole fleet instead of an O(W) python loop: the RNG
    # draws are the only remaining per-worker python work, and they are
    # one C call each.
    pad = padded - l
    perms = np.empty((w, local_ep, padded), dtype=np.int64)
    for wi in range(w):
        wid = int(worker_ids[wi]) if worker_ids is not None else wi
        for ep in range(local_ep):
            rng = np.random.default_rng(
                np.random.SeedSequence([seed, round_idx, ep, wid])
            )
            perm = rng.permutation(l)
            if drop_last:
                perms[wi, ep] = perm[:padded]
            elif pad:
                perms[wi, ep, :l] = perm
                perms[wi, ep, l:] = perm[:pad]
            else:
                perms[wi, ep] = perm
    # One gather for the fleet: [W, 1, L] rows indexed by [W, E, padded].
    gathered = np.take_along_axis(index_matrix[:, None, :], perms, axis=2)
    idx = np.ascontiguousarray(
        gathered.reshape(w, s, bs).astype(np.int32, copy=False))
    if drop_last or pad == 0:
        weight = np.ones((w, s, bs), np.float32)
    else:
        epoch_mask = np.concatenate(
            [np.ones(l, np.float32), np.zeros(pad, np.float32)]
        ).reshape(steps_per_epoch, bs)
        weight = np.tile(epoch_mask[None], (w, local_ep, 1)).reshape(w, s, bs)
    return BatchPlan(idx=idx, weight=weight)


def gather_batches(
    x: np.ndarray, y: np.ndarray, plan: BatchPlan
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Materialise [W, S, B, ...] feature / label / weight arrays from a
    plan — the host→device transfer payload for one round."""
    bx = x[plan.idx]            # [W, S, B, ...]
    by = y[plan.idx].astype(np.int32)
    return bx, by, plan.weight


def stacked_eval_batches(
    index_matrix: np.ndarray, *, batch_size: int
) -> tuple[np.ndarray, np.ndarray]:
    """Per-worker static-shape eval stacks over index rows: [W, S, B]
    gather indices + 0/1 wraparound-padding weights.  Used for the
    local-validation holdout eval (the reference's per-client val
    loader) and the per-client train-split eval
    (``avg_trainig_calculator``)."""
    w, l = index_matrix.shape
    bs = min(batch_size, l)
    steps = -(-l // bs)
    pad = steps * bs - l
    idx = (index_matrix if pad == 0
           else np.concatenate([index_matrix, index_matrix[:, :pad]], axis=1))
    weight = np.concatenate(
        [np.ones((w, l), np.float32), np.zeros((w, pad), np.float32)], axis=1)
    return (idx.reshape(w, steps, bs).astype(np.int32),
            weight.reshape(w, steps, bs))


def sharded_eval_batches(
    n: int, workers: int, *, batch_size: int
) -> tuple[np.ndarray, np.ndarray]:
    """Round-robin 1/W shard of an n-sample eval set per worker:
    [W, S, B] gather indices + 0/1 padding weights.

    The throughput-trim alternative to every worker evaluating the FULL
    set (``GossipConfig.eval_mode='sharded'``): the fleet-MEAN metric is
    an unbiased estimate built from n total sample-forwards instead of
    W·n (measured 3.1 s/round of the baseline5 wall — more than the
    training step itself), at the price of noisier PER-WORKER rows
    (~n/W samples each).  Shards are round-robin so class mix is
    near-uniform across workers for shuffled eval sets."""
    l = -(-n // workers)
    idx = np.zeros((workers, l), np.int64)
    wt = np.zeros((workers, l), np.float32)
    for i in range(workers):
        r = np.arange(i, n, workers)
        idx[i, :len(r)] = r
        wt[i, :len(r)] = 1.0
        if 0 < len(r) < l:
            # Wraparound padding from the shard's own rows; a worker
            # with NO shard rows at all (workers > n) keeps the zero
            # indices at weight 0 — valid gathers, zero contribution.
            idx[i, len(r):] = r[:l - len(r)]
    bs = min(batch_size, l)
    steps = -(-l // bs)
    pad = steps * bs - l
    if pad:
        idx = np.concatenate([idx, idx[:, :pad]], axis=1)
        wt = np.concatenate([wt, np.zeros((workers, pad), np.float32)],
                            axis=1)
    return (idx.reshape(workers, steps, bs).astype(np.int32),
            wt.reshape(workers, steps, bs))


def eval_batches(
    x: np.ndarray, y: np.ndarray, *, batch_size: int
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Static-shape eval split: [S, B, ...] with wraparound padding mask
    (shared by all workers — evaluation uses the full test set, matching
    the reference's per-client test loader over the whole test split)."""
    n = len(y)
    bs = min(batch_size, n)
    steps = -(-n // bs)
    padded = steps * bs
    pad = padded - n
    idx = np.arange(n)
    if pad:
        idx = np.concatenate([idx, idx[:pad]])
    mask = np.concatenate([np.ones(n, np.float32), np.zeros(pad, np.float32)])
    return (
        x[idx].reshape(steps, bs, *x.shape[1:]),
        y[idx].reshape(steps, bs, *y.shape[1:]).astype(np.int32),
        mask.reshape(steps, bs),
    )
