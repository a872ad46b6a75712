"""Byzantine-robust aggregation: tolerate workers that lie, not just die.

``dopt.faults`` models workers that *die* (crash/straggle/partition);
this module is the defense against workers that *lie*
(``FaultConfig.corrupt``): a single NaN, sign-flipped or norm-blown
update silently corrupts a plain mean — the steady state for
geo-distributed fleets with flaky or adversarial participants
(FusionLLM, arXiv:2410.12707; "From promise to practice",
arXiv:2410.11998).

Everything here is a jittable pure function over the engines' stacked
[W, ...] pytrees plus a 0/1 participation mask, so robust runs keep all
the execution-path guarantees of the fault subsystem (bit-reproducible,
blocked-exact, resume-exact).  Alive-counts are *data*, never shapes:
the trimmed mean / median / Krum handle a dynamic survivor count via
sorted-position weighting, so one compiled program serves every round.
That counts-are-data discipline is load-bearing beyond this module: it
is what the federated engine's fixed-width compact fault lanes and the
fused-quarantine scan carry (PR 4) reuse to keep every degraded mode
on the blocked execution path — the detection/quarantine layer's
streak state now lives on device as int32 scan carry, with the host
replaying the identical rule post-fetch for the ledger.

* ``finite_lane_mask`` — non-finite screening: a lane with ANY NaN/Inf
  leaf entry is flagged, and the engines treat it as failed for the
  round (always on for the federated mean — the non-finite guard).
* ``clip_to_ball`` — per-lane L2 clip of updates around a reference
  point (norm-bounded contribution).
* ``masked_trimmed_mean`` / ``masked_median`` — coordinate-wise robust
  statistics over the alive lanes (breakdown points trim_frac and 1/2).
* ``krum_aggregate`` — Krum / multi-Krum (Blanchard et al. 2017):
  distance-based selection, tolerates f Byzantine with n > 2f + 2.
* ``clipped_gossip_mix`` — the decentralized defense (He et al.,
  ClippedGossip): clip every neighbor deviation before applying the
  mixing weights; composes with crash/partition matrix repair because
  it consumes the already-repaired matrix as data.
"""

from __future__ import annotations

import functools
import operator

import jax
import jax.numpy as jnp
import numpy as np

from dopt.parallel.collectives import MIX_PRECISION

AGGREGATORS = ("mean", "trimmed_mean", "median", "krum", "multi_krum")


def validate_robust_config(cfg) -> None:
    """Range/enum checks for ``RobustConfig`` — fail at trainer
    construction with a clean message, not deep inside a trace."""
    if cfg.aggregator not in AGGREGATORS:
        raise ValueError(f"unknown aggregator {cfg.aggregator!r}; one of "
                         f"{AGGREGATORS}")
    if not 0.0 <= cfg.trim_frac < 0.5:
        raise ValueError(
            f"RobustConfig.trim_frac={cfg.trim_frac} must be in [0, 0.5) "
            "(trimming half from each end leaves nothing)")
    if cfg.krum_f < 0:
        raise ValueError("RobustConfig.krum_f must be >= 0")
    if cfg.multi_krum_m < 0:
        raise ValueError("RobustConfig.multi_krum_m must be >= 0")
    if cfg.clip_radius < 0:
        raise ValueError("RobustConfig.clip_radius must be >= 0")
    if cfg.quarantine_after < 0:
        raise ValueError("RobustConfig.quarantine_after must be >= 0")
    if cfg.quarantine_rounds < 1:
        raise ValueError("RobustConfig.quarantine_rounds must be >= 1")


# ---------------------------------------------------------------------
# Screening & clipping
# ---------------------------------------------------------------------

def finite_lane_mask(stacked):
    """[W] float32 flag per lane: 1.0 iff EVERY leaf entry is finite.

    The non-finite screen — one NaN anywhere in a worker's update marks
    the whole lane, because a partially-poisoned update is exactly as
    untrustworthy as a fully-poisoned one."""
    flags = [
        jnp.isfinite(leaf).all(axis=tuple(range(1, leaf.ndim)))
        if leaf.ndim > 1 else jnp.isfinite(leaf)
        for leaf in jax.tree.leaves(stacked)
    ]
    return functools.reduce(operator.and_, flags).astype(jnp.float32)


def lane_sq_norms(stacked):
    """[W] float32 squared L2 norm of each lane across all leaves."""
    parts = [
        (leaf.astype(jnp.float32) ** 2).reshape(leaf.shape[0], -1).sum(axis=1)
        for leaf in jax.tree.leaves(stacked)
    ]
    return functools.reduce(operator.add, parts)


def global_norm_f32(tree):
    """Global L2 norm of a pytree, f32-accumulated."""
    parts = [(leaf.astype(jnp.float32) ** 2).sum()
             for leaf in jax.tree.leaves(tree)]
    return jnp.sqrt(functools.reduce(operator.add, parts))


def clip_to_ball(stacked, center, radius: float):
    """Clip each lane's deviation from ``center`` to an L2 ball of
    ``radius`` (whole-model norm, like gradient clipping): a liar's
    contribution to any aggregate is bounded by the radius however it
    scales its update.  ``radius=0`` is the caller's 'off' sentinel —
    do not call with it."""
    dev = jax.tree.map(lambda x, c: x - c, stacked, center)
    n = jnp.sqrt(jnp.maximum(lane_sq_norms(dev), 1e-24))
    s = jnp.minimum(1.0, radius / n)                      # [W]
    s = jnp.where(jnp.isfinite(s), s, 0.0)

    def leaf(x, c, d):
        sc = s.reshape((-1,) + (1,) * (x.ndim - 1)).astype(x.dtype)
        return (c + sc * d).astype(x.dtype)

    return jax.tree.map(leaf, stacked, center, dev)


# ---------------------------------------------------------------------
# Robust aggregators (stacked [W, ...] + mask -> global tree, no W axis)
# ---------------------------------------------------------------------

def masked_mean(stacked, mask):
    """The reference masked average (``collectives.masked_average``
    without the mesh/wire knobs) — breakdown point 0, kept here so the
    dispatcher covers the full aggregator enum."""
    m = jnp.asarray(mask, jnp.float32)
    denom = jnp.maximum(m.sum(), 1.0)

    def leaf(x):
        mm = m.reshape((-1,) + (1,) * (x.ndim - 1)).astype(x.dtype)
        return (x * mm).sum(axis=0) / denom.astype(x.dtype)

    return jax.tree.map(leaf, stacked)


def masked_trimmed_mean(stacked, mask, trim_frac: float):
    """Coordinate-wise trimmed mean over the alive lanes.

    Per coordinate, the alive values are sorted and the k largest and k
    smallest dropped, k = floor(trim_frac · n_alive) clamped so at
    least one value survives.  Dead lanes are pushed past the alive
    block with a +inf sentinel and position-weighted out, so the
    survivor count is pure data — no dynamic shapes, one compiled
    program for every round."""
    m = jnp.asarray(mask, jnp.float32)
    n_alive = m.sum().astype(jnp.int32)
    k = jnp.minimum((trim_frac * n_alive.astype(jnp.float32))
                    .astype(jnp.int32),
                    jnp.maximum((n_alive - 1) // 2, 0))

    def leaf(x):
        mb = m.astype(bool).reshape((-1,) + (1,) * (x.ndim - 1))
        xs = jnp.sort(jnp.where(mb, x, jnp.asarray(jnp.inf, x.dtype)),
                      axis=0)
        pos = jnp.arange(x.shape[0]).reshape((-1,) + (1,) * (x.ndim - 1))
        sel = (pos >= k) & (pos < n_alive - k)
        kept = jnp.where(sel, xs, jnp.zeros((), x.dtype))  # inf·0-safe
        denom = jnp.maximum(n_alive - 2 * k, 1).astype(x.dtype)
        return kept.sum(axis=0) / denom

    return jax.tree.map(leaf, stacked)


def masked_median(stacked, mask):
    """Coordinate-wise median over the alive lanes (breakdown point
    1/2): sort with dead lanes pushed to the end, average the middle
    one/two alive positions via dynamic indexing (data, not shape)."""
    m = jnp.asarray(mask, jnp.float32)
    n_alive = jnp.maximum(m.sum().astype(jnp.int32), 1)
    lo = (n_alive - 1) // 2
    hi = n_alive // 2

    def leaf(x):
        mb = m.astype(bool).reshape((-1,) + (1,) * (x.ndim - 1))
        xs = jnp.sort(jnp.where(mb, x, jnp.asarray(jnp.inf, x.dtype)),
                      axis=0)
        a = jnp.take(xs, lo, axis=0)
        b = jnp.take(xs, hi, axis=0)
        return ((a + b) / jnp.asarray(2, x.dtype)).astype(x.dtype)

    return jax.tree.map(leaf, stacked)


def krum_scores(stacked, mask, f: int):
    """[W] Krum scores: each alive lane's summed squared distance to its
    n_alive − f − 2 closest alive peers (Blanchard et al. 2017).  Dead
    lanes and non-finite pairs score +inf."""
    leaves = jax.tree.leaves(stacked)
    flat = jnp.concatenate(
        [leaf.reshape(leaf.shape[0], -1).astype(jnp.float32)
         for leaf in leaves], axis=1)
    w = flat.shape[0]
    mb = jnp.asarray(mask, jnp.float32).astype(bool)
    n_alive = jnp.asarray(mask, jnp.float32).sum().astype(jnp.int32)
    gram = flat @ flat.T
    n2 = jnp.diagonal(gram)
    d2 = n2[:, None] + n2[None, :] - 2.0 * gram
    valid = (mb[:, None] & mb[None, :] & ~jnp.eye(w, dtype=bool)
             & jnp.isfinite(d2))
    d2 = jnp.where(valid, jnp.maximum(d2, 0.0), jnp.inf)
    ds = jnp.sort(d2, axis=1)
    c = jnp.clip(n_alive - f - 2, 1, w - 1)
    pos = jnp.arange(w)[None, :]
    score = jnp.where(pos < c, ds, 0.0).sum(axis=1)
    return jnp.where(mb, score, jnp.inf)


def krum_aggregate(stacked, mask, f: int, m: int = 1):
    """Krum (m=1) / multi-Krum selection + average.

    The m best-scored alive lanes are averaged (m=0 derives the
    multi-Krum default n_alive − f, clamped to [1, n_alive]).  Requires
    n > 2f + 2 for the selection guarantee; with fewer alive lanes the
    neighbor count clamps to 1 and the scheme degrades gracefully to
    nearest-neighbor selection."""
    scores = krum_scores(stacked, mask, f)
    mask_f = jnp.asarray(mask, jnp.float32)
    n_alive = jnp.maximum(mask_f.sum().astype(jnp.int32), 1)
    if m > 0:
        m_eff = jnp.minimum(jnp.asarray(m, jnp.int32), n_alive)
    else:
        m_eff = jnp.clip(n_alive - f, 1, n_alive)
    # rank[i] = position of lane i in the score order; +inf (dead)
    # lanes sort last, so rank < m_eff only ever selects alive lanes
    # while m_eff <= n_alive.
    rank = jnp.argsort(jnp.argsort(scores))
    sel = (rank < m_eff).astype(jnp.float32) * mask_f
    # Degenerate rounds (e.g. a lone survivor, whose only "distances"
    # are the +inf sentinels) can leave every alive lane scored +inf —
    # the index-ranked selection then misses them all.  Fall back to
    # the masked mean over the alive lanes rather than averaging an
    # empty set to zeros.
    sel = jnp.where(sel.sum() > 0, sel, mask_f)
    return masked_mean(stacked, sel)


def make_aggregator(name: str, *, trim_frac: float = 0.1, krum_f: int = 1,
                    multi_krum_m: int = 0):
    """Aggregator dispatch for the ``aggregator=`` config knob: returns
    fn(stacked, mask) -> global tree.  'mean' is NOT served here — the
    engines keep their exact pre-robust masked-average call for it, so
    the clean path stays bit-identical."""
    if name == "trimmed_mean":
        return lambda s, m: masked_trimmed_mean(s, m, trim_frac)
    if name == "median":
        return masked_median
    if name == "krum":
        return lambda s, m: krum_aggregate(s, m, krum_f, 1)
    if name == "multi_krum":
        return lambda s, m: krum_aggregate(s, m, krum_f, multi_krum_m)
    raise ValueError(f"unknown robust aggregator {name!r}; one of "
                     f"{AGGREGATORS[1:]}")


# ---------------------------------------------------------------------
# Gossip under Byzantine sends
# ---------------------------------------------------------------------

def byzantine_mix(x, x_send, w_matrix):
    """One UNDEFENDED consensus sweep under Byzantine sends:

        x_i ← W_ii · x_i + Σ_{j≠i} W_ij · x_send_j

    Receivers absorb whatever their neighbors broadcast (this is the
    plain-mean-diverges half of the threat model), but each worker's
    SELF-term reads its true state — a liar lies on the wire, its own
    carried state keeps training honestly, so it can keep lying round
    after round instead of one NaN send becoming a permanent
    self-crash.  Non-finite poison reaches exactly the senders' actual
    out-edges (a plain contraction would NaN every row via 0·NaN).
    With honest sends (x_send == x) this is exactly the dense
    consensus step."""
    wm = jnp.asarray(w_matrix, jnp.float32)
    n = wm.shape[0]
    off = wm * (1.0 - jnp.eye(n))
    diag = jnp.diagonal(wm)
    fin = finite_lane_mask(x_send)
    # Receivers with a weighted edge from a non-finite sender absorb
    # the poison; everyone else contracts over the zeroed column.
    poisoned = (off @ (1.0 - fin)) > 0.0

    def leaf(xr, xs):
        fb = fin.reshape((-1,) + (1,) * (xs.ndim - 1)).astype(bool)
        xs_z = jnp.where(fb, xs, jnp.zeros((), xs.dtype))
        keep = diag.reshape((-1,) + (1,) * (xr.ndim - 1)).astype(jnp.float32)
        y = (keep * xr.astype(jnp.float32)
             + jnp.tensordot(off, xs_z.astype(jnp.float32), axes=[[1], [0]],
                             precision=MIX_PRECISION))
        pb = poisoned.reshape((-1,) + (1,) * (xr.ndim - 1))
        y = jnp.where(pb, jnp.nan, y)
        return y.astype(xr.dtype)

    return jax.tree.map(leaf, x, x_send)


# ---------------------------------------------------------------------
# Clipped gossip (the decentralized defense)
# ---------------------------------------------------------------------

def clipped_gossip_mix(x, x_send, w_matrix, tau: float):
    """One clipped-gossip consensus sweep (He et al., ClippedGossip):

        x_i ← x_i + Σ_{j≠i} W_ij · s_ij · (x_send_j − x_i),
        s_ij = min(1, τ / ‖x_send_j − x_i‖)   (0 for non-finite sends)

    ``x`` is each worker's TRUE state, ``x_send`` what each worker
    broadcast (a Byzantine worker lies on the wire but keeps computing
    honestly — corruption never touches its own carried state).  A liar
    moves an honest worker at most W_ij·τ per round; a NaN/Inf send is
    ignored outright, its mixing weight returning to the receiver's
    self-term.  The rule consumes the round's (possibly crash- or
    partition-repaired) matrix as data, so it composes with
    ``repair_for_dropout`` / ``repair_for_partition`` unchanged.

    Returns ``(mixed, screened)``: the post-sweep states and a [W]
    float flag per SENDER — 1.0 when the send was non-finite or clipped
    by a majority of its neighbors (the quarantine layer's detection
    signal)."""
    leaves_r = jax.tree.leaves(x)
    leaves_s = jax.tree.leaves(x_send)
    flat_r = jnp.concatenate(
        [leaf.reshape(leaf.shape[0], -1).astype(jnp.float32)
         for leaf in leaves_r], axis=1)
    n = flat_r.shape[0]
    fin = finite_lane_mask(x_send)
    # Zero non-finite sends BEFORE any contraction: 0-weighted NaN
    # columns would still poison a tensordot (0 · NaN = NaN).
    x_send_z = jax.tree.map(
        lambda s: jnp.where(
            fin.reshape((-1,) + (1,) * (s.ndim - 1)).astype(bool),
            s, jnp.zeros((), s.dtype)),
        x_send)
    flat_s = jnp.concatenate(
        [leaf.reshape(leaf.shape[0], -1).astype(jnp.float32)
         for leaf in jax.tree.leaves(x_send_z)], axis=1)
    # d2[i, j] = ‖x_send_j − x_i‖² via the gram trick (no [W, W, F]).
    d2 = ((flat_r ** 2).sum(1)[:, None] + (flat_s ** 2).sum(1)[None, :]
          - 2.0 * flat_r @ flat_s.T)
    dist = jnp.sqrt(jnp.maximum(d2, 0.0))
    s = jnp.minimum(1.0, tau / jnp.maximum(dist, 1e-12))
    s = jnp.where(jnp.isfinite(s), s, 0.0)
    eye = jnp.eye(n)
    s = s * (1.0 - eye) * fin[None, :]   # no self-deviation, no poison
    wm = jnp.asarray(w_matrix, jnp.float32)
    c = wm * s                           # trust-scaled off-diag weights
    rowsum = c.sum(axis=1)               # weight actually given away

    def leaf(xr, xs):
        keep = (1.0 - rowsum).reshape(
            (-1,) + (1,) * (xr.ndim - 1)).astype(jnp.float32)
        y = (keep * xr.astype(jnp.float32)
             + jnp.tensordot(c, xs.astype(jnp.float32), axes=[[1], [0]],
                             precision=MIX_PRECISION))
        return y.astype(xr.dtype)

    mixed = jax.tree.map(leaf, x, x_send_z)
    # Sender screening: fraction of its actual (off-diagonal) neighbor
    # edges that clipped it.
    edges = (wm * (1.0 - eye)) > 0.0
    clipped = edges & (s < 1.0)
    frac = (clipped.sum(axis=0)
            / jnp.maximum(edges.sum(axis=0), 1).astype(jnp.float32))
    screened = jnp.maximum((frac > 0.5).astype(jnp.float32), 1.0 - fin)
    return mixed, screened


# ---------------------------------------------------------------------
# Quarantine bookkeeping (shared streak/sentence rule)
# ---------------------------------------------------------------------

def quarantine_step(streak: np.ndarray, until: np.ndarray,
                    ids: np.ndarray, flags: np.ndarray, t: int, *,
                    after: int, rounds: int) -> list[tuple[int, int]]:
    """One host-side detection/quarantine update over identity arrays:
    K consecutive screened participations → benched for ``rounds``; one
    clean participation resets the streak.  The same rule the engines'
    lane-keyed machinery applies (their inline copies are load-bearing
    — each is mirrored by a jnp scan-carry twin and pinned to exact
    ledger row ORDER, so they stay hand-rolled); the client registry's
    population-keyed state (``dopt.population``) calls this directly.

    ``streak``/``until`` are the identity-indexed int arrays (mutated
    in place); ``ids`` the identities that PARTICIPATED this round with
    their 0/1 ``flags``.  ``after`` <= 0 disables sentencing (streaks
    still track).  Returns [(id, until)] for the identities quarantined
    THIS call, so the caller can ledger them."""
    sentenced: list[tuple[int, int]] = []
    for j, wid in enumerate(np.asarray(ids).reshape(-1)):
        wid = int(wid)
        if float(flags[j]) > 0.5:
            streak[wid] += 1
            if after > 0 and streak[wid] >= after:
                until[wid] = int(t) + 1 + int(rounds)
                streak[wid] = 0
                sentenced.append((wid, int(until[wid])))
        else:
            streak[wid] = 0
    return sentenced
