"""Serverless gossip/consensus training (the reference's project 2).

Re-creates ``Simulator``/``DecFedAvg``/``NoConsDecFedAvg``/``FedLCon``
(``Distributed Optimization/src/simulators.py``) — and implements
``GossipLearning``, which the reference declares but leaves an empty
stub (simulators.py:215-217) — as ONE stacked-worker engine:

* N workers = one [W, ...] pytree sharded over the mesh worker axis.
* Consensus  x_i ← Σ_j W_ij x_j  = a collective (``mix_dense`` /
  ``mix_shifts_shardmap``) instead of ``Neighbors()`` passing
  state_dicts (simulators.py:91-97).
* Faithful round order (SURVEY §3.2): consensus → eval → local update,
  with two-phase synchronous semantics for free (pure functions read
  round-t weights only).
* The dataset lives on device once; each round ships only the [W, S, B]
  int32 batch plan and gathers on-device — no per-round host copies of
  the data.

Round accounting follows the reference: ``self.round`` persists across
``run()`` calls (servers.py:18,78) and time-varying schedules select
``matrices[round % len]`` (simulators.py:141-142).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from dopt.config import ExperimentConfig
from dopt.data import (eval_batches, load_dataset, make_batch_plan,
                       partition, sharded_eval_batches)
from dopt.engine.local import (_stacked_eval_scan,
                               flat_input_stacked_apply, gather_rows,
                               make_evaluator,
                               make_stacked_evaluator, make_stacked_local_update,
                               make_stacked_local_update_epochs,
                               make_stacked_local_update_gather,
                               model_objective, pick_gather_chunks,
                               prepare_holdout, validate_optimizer)
from dopt.engine.loop import HostLoop, RoundPath
from dopt.models import build_model, count_params
from dopt.parallel.collectives import (MIX_PRECISION, buckets_to_stacked,
                                        make_codec_plan,
                                        make_update_shard_spec, mix_codec_gather,
                                        mix_dense, mix_shifts,
                                        mix_update_scatter, stacked_to_buckets,
                                        where_mask)
from dopt.parallel.mesh import (make_worker_mesh, shard_over_workers,
                                shard_worker_tree, worker_axes,
                                worker_sharding)
from dopt.faults import FaultPlan, churn_ledger_rows, corrupt_update
from dopt.robust import (byzantine_mix, clipped_gossip_mix,
                         finite_lane_mask, lane_sq_norms,
                         validate_robust_config)
from dopt.topology import (MixingMatrices, build_mixing_matrices,
                           coeffs_for_matrix, repair_for_dropout,
                           repair_for_partition,
                           schedule_shift_decomposition)
from dopt.utils.metrics import History
from dopt.utils.profiling import PhaseTimers
from dopt.utils.prng import host_rng


def _reject_sequence_model(cfg: ExperimentConfig) -> None:
    """The federated/gossip engines drive image/feature datasets with
    float inputs; sequence models need int32 token batches and a
    sequence-parallel mesh — fail early with a pointer instead of an
    obscure Embed dtype error deep inside model.init."""
    if cfg.model.model.lower() == "transformer":
        raise ValueError(
            "model='transformer' is a sequence model and is not drivable by "
            "the federated/gossip engines (their datasets are image/feature "
            "tensors); use the sequence-parallel LM engine instead: "
            "SeqLMConfig + dopt.engine.SeqLMTrainer "
            "(python -m dopt.run --preset seqlm)"
        )


def random_matching_matrix(n: int, rng: np.random.Generator) -> np.ndarray:
    """GossipLearning round matrix: a random perfect matching; matched
    pairs average (w=1/2 each), unmatched (odd n) keep their weights.
    This is classic pairwise gossip — the algorithm the reference's
    empty ``GossipLearning`` stub names."""
    w = np.zeros((n, n))
    perm = rng.permutation(n)
    for k in range(0, n - 1, 2):
        i, j = perm[k], perm[k + 1]
        w[i, i] = w[j, j] = 0.5
        w[i, j] = w[j, i] = 0.5
    if n % 2:
        i = perm[-1]
        w[i, i] = 1.0
    return w


class GossipTrainer(HostLoop):
    """D-SGD / no-consensus / FedLCon / GossipLearning on the mesh.

    algorithm (cfg.gossip.algorithm):
      'dsgd'        — consensus then local update (DecFedAvg, simulators.py:133-167)
      'nocons'      — local update only (NoConsDecFedAvg, :110-131)
      'centralized' — preset: force num_users=1, local_ep=1, iid (:169-174,
                      without mutating the caller's config object)
      'fedlcon'     — eps consensus sweeps per round (:176-212, bug fixed;
                      cfg.gossip.faithful_bugs=True reproduces the
                      effectively-one-sweep behaviour)
      'gossip'      — random pairwise matching per round (the stub, implemented)
      'choco'       — CHOCO-SGD (Koloskova et al. 2019): compressed-difference
                      gossip Q(x_i − x̂_i) with error feedback; consensus step
                      x_i += γ·((W x̂)_i − x̂_i).  Beyond the reference —
                      communication-efficient decentralized training.
    """

    engine_kind = "gossip"

    def __init__(self, cfg: ExperimentConfig, *, eval_every: int = 1,
                 membership=None):
        if cfg.gossip is None:
            raise ValueError("cfg.gossip must be set for GossipTrainer")
        if membership is not None and cfg.population is not None:
            raise ValueError(
                "the serve membership overlay does not compose with the "
                "client population registry (cohort sampling already "
                "models client join/leave; a lane-level overlay would "
                "silently fight the registry's shard assignment) — drop "
                "one of the two")
        g = cfg.gossip
        if g.algorithm not in ("dsgd", "nocons", "centralized", "fedlcon",
                               "gossip", "choco"):
            raise ValueError(
                f"unknown gossip algorithm {g.algorithm!r}; one of "
                "dsgd|nocons|centralized|fedlcon|gossip|choco"
            )
        if g.eval_mode not in ("full", "sharded"):
            raise ValueError(f"unknown eval_mode {g.eval_mode!r}; "
                             "one of full|sharded")
        _reject_sequence_model(cfg)
        validate_optimizer(cfg)
        if g.algorithm == "centralized":
            # The reference's Centeralized mutates the SHARED args object
            # (simulators.py:171-173) — we derive a new frozen config.
            cfg = cfg.replace(
                data=dataclasses.replace(cfg.data, num_users=1, iid=True),
                gossip=dataclasses.replace(g, local_ep=1, algorithm="nocons"),
            )
            g = cfg.gossip
        self.cfg = cfg
        self.eval_every = eval_every
        self.round = 0
        self.history = History(cfg.name)
        # Per-epoch per-worker rows (only filled when the local holdout
        # is on): the reference's Client.history
        # (P2 clients.py:52-57 {iter, train_loss, train_acc, val_acc,
        # val_loss}), plus a 'worker' column since all clients share one
        # engine.
        self.client_history = History(cfg.name + "-clients")
        self.timers = PhaseTimers()
        # Telemetry (dopt.obs): None (default) = the exact pre-telemetry
        # host loop; set via dopt.obs.attach.  All emission sites are
        # python-gated host code after the post-fetch boundary, so the
        # compiled device programs are independent of it either way.
        self.telemetry = None
        # Serve-mode hook (dopt.serve): followers of a multi-process
        # serve fleet participate in checkpoint collectives but leave
        # the write to the leader.
        self.checkpoint_writer = True

        w = cfg.data.num_users
        self.num_workers = w
        self.mesh = make_worker_mesh(w, cfg.mesh_devices, cfg.mesh_hosts)

        # Data: load, partition, upload once.
        self.dataset = load_dataset(
            cfg.data.dataset, data_dir=cfg.data.data_dir,
            train_size=cfg.data.synthetic_train_size,
            test_size=cfg.data.synthetic_test_size, seed=cfg.seed,
            input_shape=cfg.model.input_shape,
            num_classes=cfg.model.num_classes,
        )
        _, self.index_matrix = partition(
            self.dataset.train_y, w, iid=cfg.data.iid,
            shards_per_user=cfg.data.shards, seed=cfg.seed,
        )
        # Local train/val holdout (reference train_val_test, P2
        # clients.py:19-32): training runs on the 90% sub-shard only and
        # every local epoch evaluates the worker's own val split.
        self._holdout, self._train_matrix, self._val = prepare_holdout(
            cfg, self.index_matrix, self.mesh, batch_size=g.local_bs)
        # Resident train features stay FLAT on device: TPU row-gathers
        # from [N, H, W, C] with a tiny minor dim are far slower than
        # from [N, F], and the shaped layout contaminates downstream
        # ops (see flat_input_apply).  The local-update apply fns are
        # wrapped to reshape rows at use.
        self._sample_shape = self.dataset.train_x.shape[1:]
        ntr = self.dataset.train_x.shape[0]
        self._train_x = jnp.asarray(self.dataset.train_x.reshape(ntr, -1))
        self._train_y = jnp.asarray(self.dataset.train_y)
        if g.eval_mode == "sharded":
            # Per-worker round-robin test shards ([W, S, B] stacks of
            # FLAT feature rows): the fleet-mean metric costs |test|
            # sample-forwards per eval instead of W·|test| (the full
            # mode's per-round eval exceeded the baseline5 training
            # round itself — see GossipConfig.eval_mode).
            tn = len(self.dataset.test_y)
            si, sw = sharded_eval_batches(tn, w,
                                          batch_size=max(g.local_bs, 256))
            test_flat = self.dataset.test_x.reshape(tn, -1)
            self._eval = (jnp.asarray(test_flat[si]),
                          jnp.asarray(self.dataset.test_y[si]),
                          jnp.asarray(sw))
            self._eval_full = None     # built lazily by evaluate()
        else:
            ex, ey, ew = eval_batches(self.dataset.test_x,
                                      self.dataset.test_y,
                                      batch_size=max(g.local_bs, 256))
            self._eval = (jnp.asarray(ex), jnp.asarray(ey), jnp.asarray(ew))
            self._eval_full = self._eval

        # Model + stacked state (every worker starts from the same init —
        # the reference deepcopies one global model, simulators.py:23-24).
        self.model = build_model(
            cfg.model.model, num_classes=cfg.model.num_classes,
            faithful=cfg.model.faithful, dtype=cfg.model.compute_dtype,
            stage_sizes=cfg.model.stage_sizes, decoder=cfg.model.decoder,
        )
        # A sequence model's routing counts (dopt.models.decoder): each
        # step's metric carries them beside the accuracy and the round
        # program packs their means for the history row.  () for every
        # other model: python-gated, the programs are unchanged.
        self.counters = tuple(getattr(self.model, "counters", ()))
        # A sequence model brings its own token loss (model_objective).
        sequence_model = hasattr(self.model, "loss")
        if sequence_model and cfg.data.local_holdout > 0:
            raise ValueError(
                "the local holdout's per-epoch client rows count correct "
                "predictions of a classifier; a sequence model trains on "
                "its full shard (data.local_holdout=0)")
        key = jax.random.key(cfg.seed)
        dummy = jnp.zeros((1, *cfg.model.input_shape))
        params0 = self.model.init(key, dummy)["params"]
        # param_dtype: storage dtype of the stacked worker state (bf16
        # halves HBM + collective bytes; f32 is the parity mode).
        pdt = jnp.dtype(cfg.model.param_dtype)
        params0 = jax.tree.map(lambda x: x.astype(pdt), params0)
        self.param_count = count_params(params0)
        # Broadcast to the fleet HOST-SIDE from the single-worker init:
        # fetching only |θ| from the device instead of round-tripping
        # the full W·|θ| stacked tree (1.4 GB for the 32-worker ResNet
        # — construction-time, not training-time).
        p_host = jax.device_get(params0)
        stacked = jax.tree.map(
            lambda x: np.broadcast_to(x[None], (w,) + x.shape), p_host)
        self.params = shard_worker_tree(stacked, self.mesh)
        self.momentum = shard_worker_tree(
            jax.tree.map(np.zeros_like, stacked), self.mesh
        )
        # CHOCO-SGD "public copy" state x̂ (what the fleet believes each
        # worker's params are, updated only by compressed q exchanges).
        self.x_hat = (
            shard_worker_tree(jax.tree.map(np.zeros_like, stacked), self.mesh)
            if g.algorithm == "choco" else {}
        )

        # Mixing schedule (matrices are data).
        if g.algorithm in ("dsgd", "fedlcon", "choco"):
            self.mixing: MixingMatrices | None = build_mixing_matrices(
                g.topology, g.mode, w, seed=cfg.seed, self_weight=g.self_weight,
                groups=g.hier_groups, period=g.hier_period,
            )
        else:
            self.mixing = None

        self._matching_rng = host_rng(cfg.seed, 60551)
        # Fault injection (dopt.faults.FaultPlan): crashes, stragglers
        # and partitions drawn statelessly per round on the host; the
        # mixing matrix is repaired as data and dead lanes keep their
        # state via where_mask (elastic rejoin).  ``GossipConfig.dropout``
        # is the back-compat alias for crash-only faults.
        self.faults = FaultPlan(w, cfg.faults, seed=cfg.seed,
                                dropout=g.dropout, membership=membership)
        has_faults = self.faults.active
        may_straggle = self.faults.may_straggle

        # Client population registry (dopt.population): the gossip-side
        # integration is cohort→lane DATA binding — each round the
        # stateless sampler binds ``n`` population clients onto the n
        # lanes, so lane i trains client c_i's assigned shard under
        # client c_i's batch stream while the consensus state stays
        # lane-resident (a sampled client inherits the lane's current
        # model from its previous occupant, the decentralized-FL
        # hand-off).  Client-keyed FAULT identity is a federated-engine
        # feature: gossip's crash/corrupt/link machinery is lane-keyed
        # throughout, so composing it with a per-round client rebinding
        # would silently change what "worker i" means — rejected loudly
        # instead.  population=None compiles the exact pre-change
        # programs.
        self._registry = None
        if cfg.population is not None:
            from dopt.population import (ClientRegistry,
                                         validate_population_config)

            pop = cfg.population
            validate_population_config(pop)
            if pop.cohort != w:
                raise ValueError(
                    f"gossip population mode trains every lane every "
                    f"round: set cohort == data.num_users "
                    f"(cohort={pop.cohort}, num_users={w}); wave-looped "
                    "cohorts are a federated-engine feature")
            if pop.lanes not in (None, w):
                raise ValueError(
                    f"gossip population mode binds onto the fixed "
                    f"{w}-lane fleet; lanes={pop.lanes} is a federated-"
                    "engine knob")
            if has_faults or g.dropout > 0:
                raise ValueError(
                    "gossip population mode does not compose with fault "
                    "injection (gossip fault identity is lane-keyed; a "
                    "per-round client rebinding would silently change "
                    "what 'worker i' means) — use the federated engine "
                    "for client-keyed faults")
            if cfg.robust is not None and (cfg.robust.clip_radius > 0
                                           or cfg.robust.quarantine_after
                                           > 0):
                raise ValueError(
                    "gossip population mode does not compose with the "
                    "robust layer (screen/quarantine identity is lane-"
                    "keyed, and its ledger rows would interleave "
                    "differently under blocked execution) — the "
                    "federated engine is the client-keyed path")
            if cfg.data.local_holdout > 0:
                raise ValueError(
                    "gossip population mode is incompatible with the "
                    "local holdout (per-epoch client rows are lane-"
                    "keyed) — drop one of the two")
            self._registry = ClientRegistry(pop, num_shards=w,
                                            seed=cfg.seed, lanes=w)

        # Prefetched host pipeline (dopt.data.prefetch): "on" makes the
        # blocked loops stage block b+1's plans + fault inputs while
        # block b runs on device.  "off" (default) is the exact
        # pre-change host loop — the oracle-parity mode.
        if g.prefetch not in ("off", "on"):
            raise ValueError(
                f"unknown prefetch {g.prefetch!r}; one of off|on")
        self._prefetch = g.prefetch == "on"
        # Per-round convergence diagnostics (GossipConfig.diagnostics):
        # "on" computes the diag scalar block INSIDE the compiled round
        # (it rides the packed host-metrics vector, so the blocked scan
        # carries it as one more stacked output) and emits it as
        # deterministic gauges at the post-fetch boundary, plus the
        # non-deterministic resource/compile channel when telemetry is
        # attached.  "off" (default) compiles the exact pre-change
        # programs — every use below is python-gated on it.
        if g.diagnostics not in ("off", "on"):
            raise ValueError(
                f"unknown diagnostics {g.diagnostics!r}; one of off|on")
        self._diag = g.diagnostics == "on"
        from dopt.obs.events import DIAG_GAUGES

        # The packed block's emission names: the shared five + this
        # engine's dispersion meter (round_diag's stack order).
        self._diag_keys = DIAG_GAUGES + ("consensus_distance",)
        from dopt.utils.profiling import CompileWatcher

        self._compile_watch = CompileWatcher()
        self._last_step_total = 0.0
        if self._diag and self._registry is not None:
            raise ValueError(
                "diagnostics='on' does not compose with population mode "
                "(lanes rebind to a different client cohort every round, "
                "so round-over-round lane diagnostics would mix cohort "
                "resampling noise with actual contraction) — drop one of "
                "the two")
        if self._prefetch and self._registry is not None:
            raise ValueError(
                "prefetch='on' does not compose with gossip population "
                "mode (the cohort binding mutates the registry and "
                "appends its ledger row at plan time, which a staged "
                "build must not do) — the federated engine is the "
                "prefetch-eligible population path")

        # Byzantine threat model (dopt.robust): workers can LIE on the
        # wire — their broadcast state is corrupted inside the jitted
        # round — and the defense is clipped gossip (every neighbor
        # deviation norm-clipped before the mixing weights apply) plus
        # the detection/quarantine layer.  All of it is gated on
        # ``robust_active`` so clean runs compile the exact pre-robust
        # program.
        has_corrupt = self.faults.has_corrupt
        self._has_corrupt = has_corrupt
        corrupt_mode = cfg.faults.corrupt_mode if has_corrupt else "nan"
        corrupt_scale = cfg.faults.corrupt_scale if has_corrupt else 1.0
        rcfg = cfg.robust
        if rcfg is not None:
            validate_robust_config(rcfg)
            if rcfg.aggregator != "mean":
                raise ValueError(
                    "server-side robust aggregators are a federated-engine "
                    "knob; the gossip defense is clipped mixing "
                    "(RobustConfig.clip_radius)")
        clip_tau = rcfg.clip_radius if rcfg is not None else 0.0
        self._quarantine_on = bool(rcfg is not None
                                   and rcfg.quarantine_after > 0)
        self._quarantine_after = rcfg.quarantine_after if rcfg else 0
        self._quarantine_rounds = rcfg.quarantine_rounds if rcfg else 0
        self._screen_streak = np.zeros(w, np.int64)
        self._quarantine_until = np.zeros(w, np.int64)
        robust_active = has_corrupt or clip_tau > 0 or self._quarantine_on
        self._robust_active = robust_active
        if has_corrupt:
            if cfg.faults.corrupt_mode == "stale":
                raise ValueError(
                    "corrupt_mode='stale' needs the worker's previous "
                    "update, which only the federated engine carries; "
                    "use nan|inf|scale|signflip for gossip")
            if g.algorithm not in ("dsgd", "fedlcon", "gossip"):
                raise ValueError(
                    "corrupt faults need a mixing algorithm to lie "
                    f"through (dsgd|fedlcon|gossip), not {g.algorithm!r}")
        if robust_active and g.algorithm == "choco":
            raise ValueError(
                "the robust layer does not cover choco's compressed "
                "exchange; use dsgd|fedlcon|gossip")
        if robust_active and g.comm_dtype:
            # The robust consensus paths (clipped_gossip_mix /
            # byzantine_mix) run full-precision pairwise math and never
            # consult the wire-compression knob — reject rather than
            # silently run a different experiment than configured
            # (mirrors the federated aggregator+comm_dtype reject).
            raise ValueError(
                "comm_dtype wire compression only applies to the plain "
                "consensus collectives; the robust layer (corrupt "
                "faults / clip_radius / quarantine) runs full-precision "
                "pairwise mixing — drop one of the two")
        if (clip_tau > 0 or self._quarantine_on) and g.algorithm == "nocons":
            # No consensus step means no wire to clip and no screened
            # signal to quarantine on — reject loudly rather than run
            # with a defense the user believes is active.
            raise ValueError(
                "RobustConfig clip_radius/quarantine need a mixing "
                "algorithm to act on (dsgd|fedlcon|gossip); "
                f"{cfg.gossip.algorithm!r} never communicates")

        # Lossy-link network model (dopt.faults msg_drop / msg_delay) and
        # the push-sum bias correction (GossipConfig.correction).  Both
        # route consensus through the link-matrix path: the round's
        # effective mixing becomes a [D+1, n, n] per-staleness stack
        # (dopt.topology.split_by_delay) contracted against the current
        # sends plus up-to-D-rounds-stale buffered state carried as
        # engine state.  correction="push_sum" additionally carries a
        # scalar mass per worker through the SAME (column-stochastic,
        # mass-conserving) matrices and de-biases as params/mass —
        # ratio consensus / Stochastic Gradient Push.  Everything is
        # gated on _link_mode so clean runs compile the exact
        # pre-change program.
        if g.correction not in ("none", "push_sum"):
            raise ValueError(f"unknown gossip correction {g.correction!r}; "
                             "one of none|push_sum")
        self._push_sum = g.correction == "push_sum"
        self._has_link = self.faults.has_link
        self._link_mode = self._has_link or self._push_sum
        self._delay_max = self.faults.delay_max
        if self._link_mode:
            if g.algorithm not in ("dsgd", "gossip"):
                raise ValueError(
                    "link faults (msg_drop/msg_delay) and "
                    "correction='push_sum' need a single-sweep mixing "
                    "algorithm (dsgd|gossip), not "
                    f"{g.algorithm!r}")
            if g.comm_dtype:
                raise ValueError(
                    "comm_dtype wire compression only applies to the "
                    "plain consensus collectives; the link-fault / "
                    "push-sum path runs its own per-staleness "
                    "contractions — drop one of the two")
            if clip_tau > 0:
                raise ValueError(
                    "clipped gossip does not compose with the lossy-link "
                    "consensus path yet — run clip_radius and link "
                    "faults in separate experiments")
            # Quarantine DOES compose with link faults, via the alive
            # machinery: a quarantined worker's edges are repaired out
            # of the matrix before the link drops/delays apply.  The
            # link path emits no screened flags (only finite lies reach
            # it), so the quarantine state evolves purely by expiry —
            # which is what keeps its plan-time inputs exact under
            # blocked execution.
            if has_corrupt and cfg.faults.corrupt_mode in ("nan", "inf"):
                raise ValueError(
                    "corrupt_mode='nan'/'inf' under link faults would "
                    "need byzantine_mix's poison routing, which the "
                    "per-staleness link path does not implement; use "
                    "the finite lies (scale|signflip)")

        # Fused-quarantine execution (the "everything is scan carry"
        # model): on the dense robust path the quarantine streak/until
        # state is int32 DEVICE state riding the blocked scan as carry,
        # the alive mask combination + matrix repair happen inside the
        # compiled round (dopt.topology.repair_for_dropout_jnp), and
        # the host replays the identical integer update rule post-fetch
        # for the ledger rows — so quarantined runs are blocked-eligible
        # with bit-identical per-round/blocked traces.  Link-mode
        # quarantine stays host-side plan-time data: the link path
        # screens nothing, so its quarantine state evolves by expiry
        # alone and is exactly known when the block is planned.
        self._fused_quar = self._quarantine_on and not self._link_mode
        fused_quar = self._fused_quar
        q_after = self._quarantine_after
        q_rounds = self._quarantine_rounds

        # Compiled round step.
        update_impl = "pallas" if cfg.optim.fused_update else "jnp"
        l2 = cfg.optim.weight_decay
        # Big-gather chunking for the resident-data scan paths: per-step
        # gathers cost ~250 µs of fixed overhead each on a v5e (18% of
        # device time on the headline workload) — split the plan into the
        # fewest chunks whose materialised [W, S/k, B, sample] slab fits
        # the budget and gather each chunk in one op instead.
        l_shard = self._train_matrix.shape[1]
        bs_eff = min(g.local_bs, l_shard)
        spe = -(-l_shard // bs_eff)  # steps per epoch (ceil, padded plan)
        sample_bytes = (int(np.prod(self.dataset.train_x.shape[1:]))
                        * self.dataset.train_x.dtype.itemsize)
        self._gather_chunks = pick_gather_chunks(
            g.local_ep * spe, workers=w, batch=bs_eff,
            sample_bytes=sample_bytes)
        epoch_chunks = pick_gather_chunks(
            spe, workers=w, batch=bs_eff, sample_bytes=sample_bytes)
        # Straggler-deadline granularity: the holdout's epoch loop gates
        # per EPOCH, the flat path per SGD step over the whole plan.
        self._straggle_units = g.local_ep if self._holdout else g.local_ep * spe
        # Grouped stacked-forward fast path (make_stacked_apply): the
        # whole fleet's forward as one feature-grouped conv program
        # instead of vmap-over-workers (~3× step speedup on TPU).
        from dopt.models.zoo import resolve_stacked_apply

        self._stacked_apply = resolve_stacked_apply(self.model,
                                                    cfg.model.stacked_impl)
        s_apply = self._stacked_apply
        # Flat-row adapters for everything that trains from the resident
        # train arrays (the evaluators consume shaped host-built stacks
        # and keep the raw apply; a sequence model's [T] rows are flat
        # already, and it brings its own token loss: model_objective).
        # (A fast-layout param codec that
        # hoists the per-step kernel relayout out of the scan was
        # measured and REJECTED: carried grouped-layout kernels make
        # XLA pick worse conv layouts — headline 378→401 ms/round,
        # baseline5 2410→2572 ms/round device time.)
        objective = model_objective(self.model, self._sample_shape)
        s_apply_f = (flat_input_stacked_apply(s_apply, self._sample_shape)
                     if s_apply is not None else None)
        # may_straggle keys the compiled local-update shape: the
        # with_limit variants thread a [W] work budget (epochs under the
        # holdout, SGD steps on the flat path) that freezes a straggler's
        # params/momentum at its deadline.  Fault-free configs compile
        # the exact pre-fault program.
        local = make_stacked_local_update(
            objective, lr=cfg.optim.lr, momentum=cfg.optim.momentum,
            algorithm="sgd", l2=l2, update_impl=update_impl,
            stacked_apply=s_apply_f, clip_norm=cfg.optim.clip_norm,
            with_limit=may_straggle,
        )
        local_epochs = (
            make_stacked_local_update_epochs(
                objective, lr=cfg.optim.lr,
                momentum=cfg.optim.momentum, algorithm="sgd", l2=l2,
                update_impl=update_impl, gather_chunks=epoch_chunks,
                stacked_apply=s_apply_f, clip_norm=cfg.optim.clip_norm,
                with_limit=may_straggle)
            if self._holdout else None
        )
        if s_apply_f is not None and self.mesh.size > 1:
            # The local phase is embarrassingly parallel across workers,
            # so on a multi-device mesh the grouped-stacked update runs
            # under shard_map (dopt.parallel.mesh.shard_over_workers):
            # per-device lanes, local feature-group count, zero
            # collectives.
            local = shard_over_workers(
                local, self.mesh, "w" * (6 if may_straggle else 5), "w" * 4)
            if local_epochs is not None:
                local_epochs = shard_over_workers(
                    local_epochs, self.mesh,
                    "wwwwwrrww" if may_straggle else "wwwwrrww", "www")
        use_holdout = self._holdout
        local_ep_n = g.local_ep
        full_evaluator = make_stacked_evaluator(
            objective if sequence_model else self.model.apply,
            stacked_apply=s_apply)
        if s_apply is not None and self.mesh.size > 1:
            full_evaluator = shard_over_workers(full_evaluator, self.mesh,
                                                "wrrr", "w")
        if g.eval_mode == "sharded":
            # Per-worker-data eval over [W, S, B] flat-row stacks — the
            # same [W]-dict contract as the full evaluator, so the round
            # and block programs are mode-agnostic.
            if s_apply_f is not None:
                def evaluator(p, ex, ey, ew):
                    return _stacked_eval_scan(
                        s_apply_f, p, ex.swapaxes(0, 1), ey.swapaxes(0, 1),
                        ew.swapaxes(0, 1))
                if self.mesh.size > 1:
                    evaluator = shard_over_workers(evaluator, self.mesh,
                                                   "wwww", "w")
            else:
                evaluator = jax.vmap(make_evaluator(objective))
        else:
            evaluator = full_evaluator
        self._full_evaluator = full_evaluator
        eps = 1 if (g.algorithm != "fedlcon" or g.faithful_bugs) else g.eps
        do_mix = g.algorithm in ("dsgd", "fedlcon", "gossip")
        is_choco = g.algorithm == "choco"
        mesh = self.mesh
        comm_dtype = jnp.dtype(g.comm_dtype) if g.comm_dtype else None

        # Communication substrate schedule (ExperimentConfig.comm): the
        # per-bucket wire codecs of dopt.parallel.collectives speak the
        # flat-bucket scatter representation, so CommConfig requires
        # update_sharding='scatter' — one substrate, one schedule,
        # shared with the federated engine.  None python-gates every
        # use below: default-off programs stay byte-identical.
        comm_cfg = cfg.comm
        codec_on = comm_cfg is not None and comm_cfg.codec != "none"
        if comm_cfg is not None:
            if g.update_sharding != "scatter":
                raise ValueError(
                    "the comm substrate schedule (ExperimentConfig.comm) "
                    "speaks the flat-bucket wire of "
                    "update_sharding='scatter'; set "
                    "gossip.update_sharding='scatter' to arm it (got "
                    f"update_sharding={g.update_sharding!r})")
            if g.comm_dtype and comm_cfg.wire_dtype:
                raise ValueError(
                    f"gossip.comm_dtype={g.comm_dtype!r} and "
                    f"comm.wire_dtype={comm_cfg.wire_dtype!r} both name "
                    "a wire dtype; set exactly one (comm.wire_dtype is "
                    "the substrate-schedule spelling of the same knob)")
            if codec_on and g.algorithm not in ("dsgd", "gossip"):
                raise ValueError(
                    f"comm.codec={comm_cfg.codec!r} carries a per-bucket "
                    "error-feedback residual across single-sweep "
                    "consensus rounds; use algorithm dsgd|gossip "
                    f"(got {g.algorithm!r}: fedlcon's eps sweeps would "
                    "re-encode mid-round, choco already quantizes its "
                    "own exchange, nocons|centralized|matching never "
                    "run the bucket wire)")
            if codec_on and g.comm_impl == "shift":
                raise ValueError(
                    "comm_impl='shift' ships circulant ppermute lanes; "
                    "the bucket codec speaks the gathered-bucket wire — "
                    "use comm_impl='auto'|'dense' with comm.codec")
            if codec_on and cfg.population is not None:
                raise ValueError(
                    "comm.codec with population mode would hand lane "
                    "i's quantization residual to a different client "
                    "after a cohort rebinding; run the codec on the "
                    "classic worker==lane engines (population=None)")
            if comm_cfg.wire_dtype:
                comm_dtype = jnp.dtype(comm_cfg.wire_dtype)

        # Consensus collective selection (GossipConfig.comm_impl): the
        # ppermute shift path replaces the reference's Neighbors()
        # state-dict passing (simulators.py:91-97) with O(k·|θ|) bytes of
        # ICI neighbor traffic per round instead of the dense path's
        # O(n·|θ|) all_gather.  The shift SET is static (compiled); the
        # per-round coefficients are data, so time-varying schedules and
        # dropout-repaired matrices reuse one compiled step.
        if g.comm_impl not in ("auto", "dense", "shift"):
            raise ValueError(
                f"unknown comm_impl {g.comm_impl!r}; one of auto|dense|shift")
        if g.comm_impl == "shift" and robust_active:
            raise ValueError(
                "comm_impl='shift' is incompatible with the robust layer: "
                "clipped mixing / corrupt sends need the dense pairwise "
                "path (the 'auto' default picks it)")
        if g.comm_impl == "shift" and self._link_mode:
            raise ValueError(
                "comm_impl='shift' is incompatible with link faults / "
                "push-sum: drop-repaired matrices leave the compiled "
                "shift set and the per-staleness stack needs the dense "
                "path (the 'auto' default picks it)")
        self._shift_ids: tuple[int, ...] | None = None
        if (g.comm_impl != "dense" and not robust_active
                and not self._link_mode and not codec_on
                and self.mixing is not None and (do_mix or is_choco)):
            flat_1d = len(mesh.axis_names) == 1
            extra = (0,) if self.faults.affects_matrix else ()
            ids = (schedule_shift_decomposition(self.mixing, max_shifts=None,
                                                extra_shifts=extra)
                   if flat_1d else None)
            if ids is not None and g.comm_impl == "auto":
                # Take the ppermute path only when it actually wins:
                # (a) there IS a wire — on a 1-device mesh every "shift"
                #     is a local lane slice and the dense tensordot is
                #     strictly better (one gemm vs one sliced copy of
                #     the stacked state PER shift, which OOMs ResNet-32
                #     on a single chip);
                # (b) the shift set is sparse (≤ max(3, w/2) diagonals —
                #     ring/dynamic/torus yes, complete/random no: the
                #     local mix work is linear in the shift count);
                # (c) its ICI bytes beat the all_gather with a 2× margin
                #     (shift_comm_lanes counts only the lanes shifts
                #     consume, vs the dense (n − L) remote lanes), with
                #     a floor of 3 shipped lanes so tiny rings — where
                #     the margin can't hold numerically — keep the
                #     stable ppermute routing.
                from dopt.parallel.collectives import shift_comm_lanes

                lanes = w // mesh.size
                shipped = shift_comm_lanes(ids, lanes, mesh.size)
                if (mesh.size == 1
                        or len(ids) > max(3, w // 2)
                        or (shipped > 3
                            and 2 * shipped > max(w - lanes, 1))):
                    ids = None
            if ids is not None:
                self._shift_ids = ids
            elif g.comm_impl == "shift":
                raise ValueError(
                    "comm_impl='shift' requires a flat 1-D worker mesh "
                    f"(workers={w}, mesh={mesh.shape}) and a mixing "
                    "schedule that decomposes into circulant shifts "
                    f"(topology={g.topology!r})")
        elif g.comm_impl == "shift":
            raise ValueError(
                "comm_impl='shift' needs a mixing-schedule algorithm "
                f"(dsgd|fedlcon|choco), not {g.algorithm!r}")

        shift_ids = self._shift_ids

        # Sharded weight-update/consensus hot path (ISSUE 5 tentpole):
        # update_sharding="scatter" flattens θ into size-bounded buckets
        # and runs the mixing as reduce-scatter partial contractions
        # (dense) or the sharded circulant contraction over the same
        # buckets (shift), with per-bucket collectives the XLA
        # latency-hiding scheduler can overlap with compute.  "off"
        # keeps every pre-change program byte-for-byte (python gating).
        if g.update_sharding not in ("off", "scatter"):
            raise ValueError(
                f"unknown update_sharding {g.update_sharding!r}; "
                "one of off|scatter")
        self._scatter_spec = None
        if g.update_sharding == "scatter":
            if g.algorithm not in ("dsgd", "fedlcon", "gossip", "choco"):
                raise ValueError(
                    "update_sharding='scatter' shards the consensus "
                    "mix; algorithm "
                    f"{g.algorithm!r} has no dense mixing step to "
                    "shard (dsgd|fedlcon|gossip|choco)")
            if robust_active:
                raise ValueError(
                    "update_sharding='scatter' does not compose with "
                    "the robust layer (corrupt faults / clip_radius / "
                    "quarantine run full-precision pairwise mixing on "
                    "the unsharded tree) — drop one of the two")
            if self._link_mode:
                raise ValueError(
                    "update_sharding='scatter' does not compose with "
                    "link faults / push-sum (the per-staleness "
                    "[D+1, n, n] contraction carries its own buffers) "
                    "— drop one of the two")
            if len(mesh.axis_names) != 1:
                raise ValueError(
                    "update_sharding='scatter' needs a flat 1-D worker "
                    f"mesh (got {mesh.shape}); hybrid (hosts × ici) "
                    "meshes keep the dense path")
            self._scatter_spec = make_update_shard_spec(
                stacked, fold=mesh.size,
                bucket_bytes=int(g.update_bucket_mb * (1 << 20)))
        scatter_spec = self._scatter_spec

        # Per-bucket wire schedule + error-feedback residual.  The plan
        # is compiled structure (built once from the spec); the residual
        # is carried engine state ("comm_residual" in checkpoints) —
        # round −1's residual is defined as zero, so codec round 0
        # encodes exactly v = x.  Built from fresh zeros: round_fn
        # donates the carry, and a donated input must never alias the
        # init tree.
        self._codec_plan = None
        self._codec_on = codec_on
        self._comm_res: object = ()
        codec_plan = None
        comm_key = None
        comm_ef = True
        if comm_cfg is not None and scatter_spec is not None:
            self._codec_plan = make_codec_plan(
                scatter_spec, codec=comm_cfg.codec,
                wire_dtype=comm_cfg.wire_dtype,
                byte_budget=int(comm_cfg.byte_budget_mb * (1 << 20)),
                min_codec_bytes=comm_cfg.min_codec_bytes,
                chunk=comm_cfg.chunk)
            codec_plan = self._codec_plan
            comm_ef = comm_cfg.error_feedback == "on"
        if codec_on:
            comm_key = jax.random.key(cfg.seed ^ 0xC0DEC)
            widths = [b - a for a, b in zip(scatter_spec.bounds,
                                            scatter_spec.bounds[1:])]
            self._comm_res = shard_worker_tree(
                tuple(np.zeros((w, wd), np.float32) for wd in widths),
                self.mesh)

        # Asynchronous (staleness-1) gossip (GossipConfig.mixing): round
        # t's mix reads the PREVIOUS round's neighbor state — x_i ←
        # W_ii·x_i(t) + Σ_{j≠i} W_ij·x_j(t−1) — so round r's neighbor
        # communication fully overlaps round r+1's compute.  The
        # previous-round buffer is carried engine state ("async_prev"):
        # a double-buffered scan carry under blocked execution and a
        # checkpoint array on resume.  "sync" (default) python-gates
        # every use below, so it compiles the exact pre-change programs.
        if g.mixing not in ("sync", "async"):
            raise ValueError(
                f"unknown gossip mixing {g.mixing!r}; one of sync|async")
        self._async = g.mixing == "async"
        if self._async:
            if g.algorithm != "dsgd":
                raise ValueError(
                    "mixing='async' only applies to the single-sweep "
                    f"dsgd consensus, not {g.algorithm!r}: fedlcon's eps "
                    "sweeps and choco's compressed exchange have no "
                    "staleness-1 diag/off-diag split, and matching/"
                    "nocons have no static schedule to stale against")
            if robust_active:
                raise ValueError(
                    "mixing='async' does not compose with the robust "
                    "layer (corrupt faults / clip_radius / quarantine "
                    "screen the CURRENT round's sends; a stale mix has "
                    "no current wire to screen) — drop one of the two")
            if self._link_mode:
                raise ValueError(
                    "mixing='async' does not compose with link faults / "
                    "push-sum (the per-staleness [D+1, n, n] stack "
                    "already models delayed state; staleness-1 is its "
                    "D=1 special case) — drop one of the two")
            if g.update_sharding == "scatter":
                raise ValueError(
                    "mixing='async' does not compose with "
                    "update_sharding='scatter' (the bucketed partial "
                    "contractions assume one source tree; the async "
                    "diag/off-diag split reads two) — drop one of "
                    "the two")
            if cfg.population is not None:
                raise ValueError(
                    "mixing='async' does not compose with population "
                    "mode (a stale neighbor read would cross a cohort "
                    "rebinding — lane i's previous-round state belongs "
                    "to a different client) — drop one of the two")
        is_async = self._async
        # Round −1's state is defined as the shared init, so async
        # round 0 mixes exactly what sync round 0 mixes.  Built fresh
        # from the host tree: round_fn donates params, and the prev
        # buffer must never alias a donated input.
        self._async_prev: object = (
            shard_worker_tree(stacked, self.mesh) if self._async else {})

        # Fused mix+update epilogue (GossipConfig.fused_update): the
        # round's consensus contraction and the previous round's local
        # displacement land in ONE Pallas pass over the flat-bucket
        # UpdateShardSpec layout —  q_t = W_t·q_{t-1} − fbuf_{t-1}  with
        # fbuf_{t-1} = q_{t-1} − p'_{t-1}  carried engine state (the
        # D-PSGD update ordering, arXiv:1705.09056: the local step folds
        # in UNMIXED, so the trajectory is a documented variant of —
        # allclose to, not bit-equal with — the default mix(p')
        # ordering).  "off" (default) python-gates every use below and
        # compiles the exact pre-change programs.
        if g.fused_update not in ("off", "on"):
            raise ValueError(
                f"unknown fused_update {g.fused_update!r}; one of off|on")
        self._fused_on = g.fused_update == "on"
        if self._fused_on:
            if g.algorithm not in ("dsgd", "gossip"):
                raise ValueError(
                    "fused_update='on' fuses the single dense consensus "
                    f"sweep with the update; algorithm {g.algorithm!r} "
                    "has no such sweep to fuse (dsgd|gossip: fedlcon's "
                    "eps sweeps re-enter the matrix, choco exchanges "
                    "compressed deltas, nocons/centralized never mix)")
            if robust_active:
                raise ValueError(
                    "fused_update='on' does not compose with the robust "
                    "layer (corrupt faults / clip_radius / quarantine "
                    "screen the wire BEFORE mixing; the fused epilogue "
                    "contracts the carried state directly) — drop one "
                    "of the two")
            if self._link_mode:
                raise ValueError(
                    "fused_update='on' does not compose with link "
                    "faults / push-sum (the per-staleness [D+1, n, n] "
                    "contraction carries its own mass/staleness "
                    "buffers) — drop one of the two")
            if self._async:
                raise ValueError(
                    "fused_update='on' does not compose with "
                    "mixing='async' (the staleness-1 diag/off-diag "
                    "split reads two source trees; the fused "
                    "contraction reads one) — drop one of the two")
            if g.update_sharding == "scatter":
                raise ValueError(
                    "update_sharding='scatter' already restructures the "
                    "consensus/update hot path; fused_update='on' is "
                    "the single-device fusion of the same epilogue — "
                    "drop one of the two")
            if g.comm_dtype:
                raise ValueError(
                    "comm_dtype wire compression only applies to the "
                    "plain consensus collectives; the fused epilogue "
                    "contracts at f32 in one HBM pass — drop one of "
                    "the two")
            if g.comm_impl == "shift":
                raise ValueError(
                    "comm_impl='shift' is incompatible with "
                    "fused_update='on': the fused epilogue is one dense "
                    "[n, n] contraction, and the ppermute shift "
                    "decomposition has no single-pass fused form")
            if cfg.population is not None:
                raise ValueError(
                    "fused_update='on' does not compose with population "
                    "mode (the displacement buffer is lane state; a "
                    "per-round client rebinding would hand lane i's "
                    "displacement to a different client) — drop one of "
                    "the two")
            if self.mesh.size > 1:
                raise ValueError(
                    "fused_update='on' needs a single-device worker "
                    f"mesh (got {self.mesh.shape}): the Pallas epilogue "
                    "contracts the full worker axis in one kernel call; "
                    "multi-device meshes keep the dense or scatter "
                    "paths")
        fused_on = self._fused_on
        fused_spec = None
        fused_mix_update = None
        self._fused_spec = None
        # The displacement buffer: round −1's local step is defined as
        # zero, so fused round 0 contracts exactly what the default
        # round 0 mixes.  Built from fresh zeros — round_fn donates it,
        # and a donated input must never alias the init tree.
        self._fused_buf: object = {}
        if self._fused_on:
            from dopt.ops.fused_update import fused_mix_update

            self._fused_spec = make_update_shard_spec(
                stacked, fold=self.mesh.size,
                bucket_bytes=int(g.update_bucket_mb * (1 << 20)))
            self._fused_buf = shard_worker_tree(
                jax.tree.map(np.zeros_like, stacked), self.mesh)
            fused_spec = self._fused_spec

        def mix_once(x, arg):
            """One consensus sweep; ``arg`` is the [n, n] matrix (dense)
            or the [k, n] coefficient table (shift) for the round."""
            if scatter_spec is not None:
                return mix_update_scatter(x, arg, mesh, scatter_spec,
                                          shift_ids=shift_ids,
                                          comm_dtype=comm_dtype)
            if shift_ids is not None:
                return mix_shifts(x, shift_ids, arg, mesh, comm_dtype)
            return mix_dense(x, arg, mesh, comm_dtype)

        def codec_mix(params, cres, w_matrix, t):
            """One compressed consensus sweep over the flat buckets:
            per-bucket encode(v = x + e) → packed all-gather → local
            decode → mixing-row contraction (mix_codec_gather), with
            the quantization residual fed back next round.  Draws are a
            pure function of (round, bucket, global lane) — fold-in
            keyed, never split — so blocked, per-round, and resumed
            runs encode identical bits."""
            buckets = stacked_to_buckets(params, scatter_spec)
            key = jax.random.fold_in(comm_key, t)
            mixed, new_res = mix_codec_gather(buckets, list(cres),
                                              w_matrix, mesh, codec_plan,
                                              key)
            if not comm_ef:
                new_res = [jnp.zeros_like(r) for r in new_res]
            return buckets_to_stacked(mixed, scatter_spec), tuple(new_res)

        def mix_consensus(x, arg):
            """eps sweeps (FedLCon, with the stale-accumulation bug
            fixed: each sweep reads the previous sweep's output)."""
            if eps == 1:
                return mix_once(x, arg)

            def body(c, _):
                return mix_once(c, arg), None

            out, _ = jax.lax.scan(body, x, None, length=eps)
            return out

        def async_mix(params, prev, w_off, wdiag):
            """One staleness-1 consensus sweep: the self-term reads the
            CURRENT params, every neighbor term reads the PREVIOUS
            round's state.  ``w_off`` is the zero-diagonal mixing
            argument ([n, n] matrix or [k, n] shift-coefficient table)
            and ``wdiag`` the [n] diagonal weights, split host-side
            AFTER all matrix repairs so a departed lane degrades to
            diag=1 / off-diag=0 — a pure local step.  The off-diagonal
            contraction reuses the synchronous collective verbatim
            (dense or ppermute-shift); only its input tree is one round
            stale."""
            neighbors = mix_once(prev, w_off)

            def fold(p, nb):
                d = wdiag.astype(jnp.float32).reshape(
                    (-1,) + (1,) * (p.ndim - 1))
                return (d * p.astype(jnp.float32)
                        + nb.astype(jnp.float32)).astype(p.dtype)

            return jax.tree.map(fold, params, neighbors)

        if is_choco:
            from dopt.ops.compression import make_compressor

            compressor = make_compressor(g.compression, g.compression_ratio,
                                         qsgd_levels=g.qsgd_levels)
            real_compression = (g.compression == "qsgd"
                                or (g.compression in ("topk", "randk")
                                    and g.compression_ratio < 1.0))
            if g.choco_gamma >= 1.0 and real_compression:
                import warnings

                warnings.warn(
                    "choco_gamma >= 1 with a real compressor can diverge: "
                    "CHOCO-SGD theory scales γ down with the compressor's "
                    "contraction factor (try γ ≈ 0.1·compression_ratio)",
                    stacklevel=2)
            choco_gamma = g.choco_gamma
            choco_key = jax.random.key(cfg.seed ^ 0x0C0C0)

        def choco_mix(params, x_hat, w_matrix, alive, t):
            """One CHOCO-SGD gossip exchange (Koloskova et al. 2019).
            Communication object: q = Q(x_i − x̂_i) only (error feedback
            lives in the uncommunicated residual); every worker then
            advances the shared public-copy table and takes the
            consensus step  x_i += γ·((W x̂)_i − x̂_i)."""
            key = jax.random.fold_in(choco_key, t)
            diff = jax.tree.map(lambda a, b: a - b, params, x_hat)
            q = compressor(diff, key)
            if has_faults:
                # Dead workers send nothing: their public copy freezes.
                q = where_mask(alive, q, jax.tree.map(jnp.zeros_like, q))
            x_hat = jax.tree.map(lambda a, b: a + b, x_hat, q)
            mixed = mix_once(x_hat, w_matrix)
            new_p = jax.tree.map(
                lambda p, mx, xh: p + (choco_gamma * (mx - xh)).astype(p.dtype),
                params, mixed, x_hat)
            return new_p, x_hat

        def zeros_eval():
            z = jnp.zeros(self.num_workers)
            return {"acc": z, "loss_sum": z, "loss_mean": z, "count": z}

        counters = self.counters

        def split_counts(accs):
            """A sequence model's step metric is a dict: its accuracy,
            and the routing counts of ``counters`` — returned as their
            [K] means over workers and steps for the round's history
            row (None for every other model)."""
            if not counters:
                return accs, None
            return accs["acc"], jnp.stack([accs[k].mean() for k in counters])

        def train_metrics(losses, accs, alive):
            """Mean over steps per worker, then over ALIVE workers only."""
            if not has_faults:
                return losses.mean(), accs.mean()
            denom = jnp.maximum(alive.sum(), 1.0)
            return ((losses.mean(axis=1) * alive).sum() / denom,
                    (accs.mean(axis=1) * alive).sum() / denom)

        diag_on = self._diag
        # [W] per-lane squared L2 over a lane-leading pytree — the same
        # f32-accumulated reduction the robust screen uses.
        _lane_sq = lane_sq_norms

        def round_diag(p_new, m_new, p_start, losses, alive):
            """[6] f32 per-round diagnostics (dopt.obs.events.DIAG_GAUGES
            + consensus_distance), computed ON DEVICE from the round's
            CARRIED state so per-round and blocked execution can never
            diverge: global L2 of the round's displacement
            ||p_new − p_start|| (dead lanes carry their state — zero
            displacement), of the carried momentum (the velocity — the
            smoothed-gradient meter), and of the carried params; the
            lane train-loss mean and max−min spread; and the true
            per-round consensus distance mean_i ||p_i − p̄||.

            All six reduce over the DIAGNOSABLE lanes: alive AND
            carrying finite state/loss.  A screened Byzantine liar
            keeps its poisoned params in its own lane (quarantine is
            the defense; the aggregation mask is the protection) — one
            NaN lane must not blind every fleet-health meter, so
            non-finite lanes drop out of the reductions.  The mask is
            computed from the same carried data on every execution
            path, so it is itself deterministic."""
            upd_sq = _lane_sq(jax.tree.map(
                lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
                p_new, p_start))
            m_sq = _lane_sq(m_new)
            p_sq = _lane_sq(p_new)
            lane = losses.mean(axis=1).astype(jnp.float32)
            ok = (alive * jnp.isfinite(upd_sq) * jnp.isfinite(m_sq)
                  * jnp.isfinite(p_sq) * jnp.isfinite(lane))
            denom = jnp.maximum(ok.sum(), 1.0)
            upd = jnp.sqrt((jnp.where(ok > 0, upd_sq, 0.0)).sum())
            gn = jnp.sqrt((jnp.where(ok > 0, m_sq, 0.0)).sum())
            pn = jnp.sqrt((jnp.where(ok > 0, p_sq, 0.0)).sum())
            lmean = (jnp.where(ok > 0, lane, 0.0)).sum() / denom
            lmax = jnp.where(ok > 0, lane, -jnp.inf).max()
            lmin = jnp.where(ok > 0, lane, jnp.inf).min()
            spread = jnp.where(ok.sum() > 0, lmax - lmin, 0.0)
            sq = None
            for x in jax.tree.leaves(p_new):
                xf = x.astype(jnp.float32)
                okx = ok.reshape((-1,) + (1,) * (xf.ndim - 1))
                xf0 = jnp.where(okx > 0, xf, 0.0)
                bar = xf0.sum(axis=0) / denom
                d = (xf0 - bar[None] * okx).reshape(xf.shape[0], -1)
                s = (d * d).sum(axis=1)
                sq = s if sq is None else sq + s
            cd = (jnp.where(ok > 0, jnp.sqrt(sq), 0.0)).sum() / denom
            return jnp.stack([upd, gn, pn, lmean, spread, cd])

        @jax.named_scope("dopt_local")
        def local_phase(params, mom, idx, bweight, train_x, train_y,
                        vidx, vw, limits):
            """The per-round local-training phase: flat step scan on the
            full shard, or (holdout mode) the reference's epoch loop with
            per-epoch local-val eval.  Returns (p, m, losses, accs, em)
            where losses/accs are per-step [W, S] or per-epoch [W, E] —
            either way ``mean(axis=1)`` is the round's train metric —
            and em carries the per-epoch history arrays ({} when off).
            ``limits`` is the [W] straggler work budget, consumed only
            when the plan can straggle (ignored otherwise)."""
            if use_holdout:
                se = idx.shape[1] // local_ep_n
                idx_e = idx.reshape(idx.shape[0], local_ep_n, se, idx.shape[2])
                bw_e = bweight.reshape(idx_e.shape)
                if may_straggle:
                    p_t, m_t, em = local_epochs(params, mom, idx_e, bw_e,
                                                limits, train_x, train_y,
                                                vidx, vw)
                else:
                    p_t, m_t, em = local_epochs(params, mom, idx_e, bw_e,
                                                train_x, train_y, vidx, vw)
                return p_t, m_t, em["train_loss"], em["train_acc"], em
            bx, by = gather_rows(train_x, train_y, idx)
            if may_straggle:
                p_t, m_t, losses, accs = local(params, mom, bx, by, bweight,
                                               limits)
            else:
                p_t, m_t, losses, accs = local(params, mom, bx, by, bweight)
            return p_t, m_t, losses, accs, {}

        def pack_host_metrics(tl, ta, evalm, em, screened, diag=None,
                              counts=None):
            """Everything the host reads per round, as ONE flat f32
            vector — every device→host fetch synchronises with the
            device, so the round's metrics
            (train loss/acc, fleet-mean eval, the robust layer's
            screened flags, and the per-epoch client-history block under
            the holdout) travel in a single transfer.  Layout (mirrored
            by ``_unpack_host_metrics``): [tl, ta, mean(acc),
            mean(loss_mean)] + [W] screened (robust runs only) +
            4×[W·E] em blocks + [K] model counts (sequence models)."""
            parts = [tl[None], ta[None],
                     jnp.mean(evalm["acc"])[None],
                     jnp.mean(evalm["loss_mean"])[None]]
            if robust_active:
                parts.append(screened)
            if use_holdout:
                parts += [em["train_loss"].ravel(), em["train_acc"].ravel(),
                          em["val_acc"].ravel(),
                          em["val_loss_mean"].ravel()]
            if counters:
                parts.append(counts)
            if diag_on:
                # Diagnostics block travels LAST so every earlier
                # offset (_unpack_host_metrics) is layout-stable.
                parts.append(diag)
            return jnp.concatenate(
                [p.astype(jnp.float32) for p in parts])

        def consensus_phase(params, x_hat, w_matrix, alive, t, cmask,
                            prev=None, wdiag=None):
            """The round's consensus step, with the Byzantine sends
            injected and (when clip_tau > 0) clipped.  A liar corrupts
            only what it BROADCASTS (``x_send``) — its own carried state
            keeps training honestly, which is the Byzantine model: lies
            on the wire, not a crashed computation.  Returns (params,
            x_hat, [W] screened sender flags).

            Under ``mixing='async'`` (``prev`` is a traced tree, never
            None) the sweep is the staleness-1 split instead:
            ``w_matrix`` carries the off-diagonal argument, ``wdiag``
            the diagonal weights, and the neighbor terms read ``prev``
            — the previous round's entry state."""
            screened = jnp.zeros(w, jnp.float32)
            if prev is not None:
                return (async_mix(params, prev, w_matrix, wdiag), x_hat,
                        screened)
            if is_choco:
                params, x_hat = choco_mix(params, x_hat, w_matrix, alive, t)
                return params, x_hat, screened
            if not do_mix:
                return params, x_hat, screened
            if not robust_active:
                return mix_consensus(params, w_matrix), x_hat, screened
            x_send = (corrupt_update(params, cmask, corrupt_mode,
                                     corrupt_scale)
                      if has_corrupt else params)
            if clip_tau > 0:
                params, screened = clipped_gossip_mix(params, x_send,
                                                      w_matrix, clip_tau)
                # FedLCon's extra sweeps re-read honest current states
                # (the lie already entered — and was clipped — in sweep
                # one).
                for _ in range(eps - 1):
                    params, _ = clipped_gossip_mix(params, params,
                                                   w_matrix, clip_tau)
            else:
                # Undefended mixing of corrupted sends — the
                # plain-mean-diverges half of the threat model.
                # Self-terms read honest state (a liar poisons its
                # NEIGHBORS, not its own computation); FedLCon's extra
                # sweeps re-mix the already-absorbed result.
                screened = 1.0 - finite_lane_mask(x_send)
                params = byzantine_mix(params, x_send, w_matrix)
                for _ in range(eps - 1):
                    params = mix_once(params, w_matrix)
            return params, x_hat, screened

        def effective_inputs(w_matrix, alive, quar, cmask):
            """Fused-quarantine input adjustment, ON DEVICE (both
            execution paths run this, which is what makes them
            bit-identical): fold the quarantine mask into alive, mute
            quarantined liars, and repair the matrix for the combined
            dead set — skipping the repair division on all-alive
            rounds, mirroring the host path's ``alive.min() < 1``
            guard.  A no-op (python-level) without fused quarantine, so
            every other configuration compiles the pre-change
            program."""
            if not fused_quar:
                return w_matrix, alive, cmask
            from dopt.topology import repair_for_dropout_jnp

            alive = alive * (1.0 - quar)
            if has_corrupt:
                cmask = cmask * (1.0 - quar)
            rep = repair_for_dropout_jnp(w_matrix, alive)
            w_matrix = jnp.where(alive.min() >= 1.0, w_matrix, rep)
            return w_matrix, alive, cmask

        def quarantine_update(streak, until, scr, alive, t):
            """Post-round screen feedback as int32 device math — the
            exact jnp mirror of ``_apply_screen_feedback``: a screened
            round extends the streak (K in a row triggers the bench), a
            clean ALIVE round resets it."""
            flagged = scr > 0.5
            streak2 = jnp.where(flagged, streak + 1,
                                jnp.where(alive > 0, 0, streak))
            trigger = flagged & (streak2 >= q_after)
            until = jnp.where(trigger, t + 1 + q_rounds, until)
            streak = jnp.where(trigger, 0, streak2)
            return streak, until

        def round_fn(params, mom, x_hat, w_matrix, alive, limits, t, idx,
                     bweight, train_x, train_y, ex, ey, ew, vidx, vw,
                     do_eval, cmask=None, quar=None, prev=None,
                     wdiag=None, fbuf=None, cres=None):
            # Async: this round's ENTRY state is what the neighbors
            # read NEXT round — it becomes the new prev buffer.
            entry = params if prev is not None else None
            w_matrix, alive, cmask = effective_inputs(w_matrix, alive,
                                                      quar, cmask)
            if fused_on:
                # ONE HBM pass over the flat buckets:
                # q_t = W_t·q_{t-1} − fbuf_{t-1} (mix + pending local
                # displacement fused; ``params`` carries the POST-MIX
                # state q, the buffer its distance to the post-local
                # endpoint).
                params = fused_mix_update(params, fbuf, w_matrix,
                                          fused_spec, lr=1.0)
                screened = jnp.zeros(w, jnp.float32)
            elif codec_on:
                # Compressed wire: the codec replaces the round's one
                # consensus sweep (eps==1 — the validation pins it) and
                # threads the error-feedback residual carry.
                params, cres = codec_mix(params, cres, w_matrix, t)
                screened = jnp.zeros(w, jnp.float32)
            else:
                params, x_hat, screened = consensus_phase(
                    params, x_hat, w_matrix, alive, t, cmask, prev=prev,
                    wdiag=wdiag)
            evalm = jax.lax.cond(
                do_eval,
                lambda: evaluator(params, ex, ey, ew),
                zeros_eval,
            )
            p_t, m_t, losses, accs, em = local_phase(
                params, mom, idx, bweight, train_x, train_y, vidx, vw,
                limits)
            if has_faults:
                # Dead workers skip the local update (their lanes compute
                # and are discarded — static shapes).
                p_t = where_mask(alive, p_t, params)
                m_t = where_mask(alive, m_t, mom)
            accs, counts = split_counts(accs)
            tl, ta = train_metrics(losses, accs, alive)
            # ``params`` is the post-consensus state here, so the diag
            # update norm measures the local-training displacement.
            diag = (round_diag(p_t, m_t, params, losses, alive)
                    if diag_on else None)
            packed = pack_host_metrics(tl, ta, evalm, em, screened, diag,
                                       counts)
            if fused_on:
                # Next round's contraction folds this displacement in.
                # Dead lanes carried q (p_t == params) → a zero row:
                # the lane freezes through the next repaired mix.
                new_fbuf = jax.tree.map(lambda a, b: a - b, params, p_t)
                return params, m_t, x_hat, new_fbuf, packed
            if codec_on:
                return p_t, m_t, x_hat, cres, packed
            if prev is not None:
                return p_t, m_t, x_hat, entry, packed
            return p_t, m_t, x_hat, packed

        # Donating the displacement/residual buffers (armed runs only —
        # the kwarg-name donation keeps the default path's jit params,
        # and therefore its fingerprinted programs, byte-identical)
        # lets XLA alias the new carry into the old carry's pages: the
        # round carry costs zero extra HBM over the plain path.
        _donate_names = (("fbuf",) if fused_on else ())
        _donate_names += (("cres",) if codec_on else ())
        _fused_donate = ({"donate_argnames": _donate_names}
                         if _donate_names else {})
        self._round_fn = jax.jit(round_fn, donate_argnums=(0, 1, 2),
                                 **_fused_donate)
        self._sharding = worker_sharding(self.mesh)

        # Fused multi-round block path (lax.scan over rounds in ONE jit).
        self._evaluator = evaluator
        self._do_mix, self._eps = do_mix, eps
        self._local_gather = make_stacked_local_update_gather(
            objective, lr=cfg.optim.lr, momentum=cfg.optim.momentum,
            algorithm="sgd", l2=l2, update_impl=update_impl,
            gather_chunks=self._gather_chunks, stacked_apply=s_apply_f,
            clip_norm=cfg.optim.clip_norm, with_limit=may_straggle,
        )
        if s_apply_f is not None and self.mesh.size > 1:
            self._local_gather = shard_over_workers(
                self._local_gather, self.mesh,
                "wwwwwrr" if may_straggle else "wwwwrr", "w" * 4)
        local_g = jax.named_scope("dopt_local")(self._local_gather)
        ev = self._evaluator

        def block_fn(params, mom, x_hat, w_mats, alive, limits, ts, idx, bw,
                     is_eval, train_x, train_y, ex, ey, ew, vidx, vw,
                     cmasks=None, streak=None, until=None, prev=None,
                     wdiags=None, fbuf=None, cres=None):
            """k rounds fused into one lax.scan dispatch (jit retraces per
            distinct k).  Each iteration is one full reference round with
            the SAME phase order as the per-round path — consensus →
            eval (on flagged rounds only) → local epochs — so history
            rows are directly comparable across block settings.  The
            minibatch gather happens inside the step scan from the
            resident train arrays; compile cost is O(1) in k.  Under
            corrupt faults the per-round corrupt masks ride the scan as
            one more stacked input; under fused quarantine the int32
            streak/until state rides the CARRY (readmission at round
            start, screen feedback after the round — the same order the
            per-round host loop applies), so quarantined runs fuse
            without surfacing flags to the host mid-block."""

            def body(carry, xs):
                pv = wd_t = fb = cr = None
                if fused_quar:
                    p, m, xh, stk, unt = carry
                elif is_async:
                    # Double-buffered staleness carry: pv is the
                    # previous round's entry state; this round's entry
                    # replaces it after the mix.
                    p, m, xh, pv = carry
                    stk = unt = None
                elif fused_on:
                    # Fused carry: p is the POST-MIX state q, fb the
                    # displacement to the post-local endpoint.
                    p, m, xh, fb = carry
                    stk = unt = None
                elif codec_on:
                    # Codec carry: cr is the per-bucket error-feedback
                    # residual the next round's encode folds back in.
                    p, m, xh, cr = carry
                    stk = unt = None
                else:
                    p, m, xh = carry
                    stk = unt = None
                if is_async:
                    (w_t, alive_t, lim_t, t_t, idx_t, bw_t, ev_t,
                     wd_t) = xs
                    cm_t = None
                elif has_corrupt:
                    w_t, alive_t, lim_t, t_t, idx_t, bw_t, ev_t, cm_t = xs
                else:
                    w_t, alive_t, lim_t, t_t, idx_t, bw_t, ev_t = xs
                    cm_t = None
                entry = p if is_async else None
                if fused_quar:
                    # Round-start readmission (mirrors _round_inputs):
                    # an expired sentence clears the bench + streak.
                    expired = (unt != 0) & (t_t >= unt)
                    unt = jnp.where(expired, 0, unt)
                    stk = jnp.where(expired, 0, stk)
                    quar_t = (unt > t_t).astype(jnp.float32)
                    w_t, alive_t, cm_t = effective_inputs(w_t, alive_t,
                                                          quar_t, cm_t)
                if fused_on:
                    p = fused_mix_update(p, fb, w_t, fused_spec, lr=1.0)
                    scr = jnp.zeros(w, jnp.float32)
                elif codec_on:
                    p, cr = codec_mix(p, cr, w_t, t_t)
                    scr = jnp.zeros(w, jnp.float32)
                else:
                    p, xh, scr = consensus_phase(p, xh, w_t, alive_t, t_t,
                                                 cm_t, prev=pv, wdiag=wd_t)
                evalm = jax.lax.cond(ev_t, lambda: ev(p, ex, ey, ew), zeros_eval)
                if use_holdout:
                    p_t, m_t, losses, accs, em = local_phase(
                        p, m, idx_t, bw_t, train_x, train_y, vidx, vw, lim_t)
                elif may_straggle:
                    p_t, m_t, losses, accs = local_g(p, m, idx_t, bw_t, lim_t,
                                                     train_x, train_y)
                    em = {}
                else:
                    p_t, m_t, losses, accs = local_g(p, m, idx_t, bw_t,
                                                     train_x, train_y)
                    em = {}
                if has_faults:
                    p_t = where_mask(alive_t, p_t, p)
                    m_t = where_mask(alive_t, m_t, m)
                accs, counts = split_counts(accs)
                tl, ta = train_metrics(losses, accs, alive_t)
                diag = (round_diag(p_t, m_t, p, losses, alive_t)
                        if diag_on else None)
                packed = pack_host_metrics(tl, ta, evalm, em, scr, diag,
                                           counts)
                if fused_quar:
                    stk, unt = quarantine_update(stk, unt, scr, alive_t,
                                                 t_t)
                    return (p_t, m_t, xh, stk, unt), packed
                if is_async:
                    return (p_t, m_t, xh, entry), packed
                if fused_on:
                    new_fb = jax.tree.map(lambda a, b: a - b, p, p_t)
                    return (p, m_t, xh, new_fb), packed
                if codec_on:
                    return (p_t, m_t, xh, cr), packed
                return (p_t, m_t, xh), packed

            xs = [w_mats, alive, limits, ts, idx, bw, is_eval]
            if has_corrupt:
                xs.append(cmasks)
            if is_async:
                xs.append(wdiags)
            if fused_quar:
                carry0 = (params, mom, x_hat, streak, until)
            elif is_async:
                carry0 = (params, mom, x_hat, prev)
            elif fused_on:
                carry0 = (params, mom, x_hat, fbuf)
            elif codec_on:
                carry0 = (params, mom, x_hat, cres)
            else:
                carry0 = (params, mom, x_hat)
            carry, packed = jax.lax.scan(body, carry0, tuple(xs))
            if fused_quar:
                return (*carry, packed)
            if is_async:
                params, mom, x_hat, prev = carry
                return params, mom, x_hat, prev, packed
            if fused_on:
                params, mom, x_hat, fbuf = carry
                return params, mom, x_hat, fbuf, packed
            if codec_on:
                params, mom, x_hat, cres = carry
                return params, mom, x_hat, cres, packed
            params, mom, x_hat = carry
            return params, mom, x_hat, packed

        self._block_fn = jax.jit(block_fn, donate_argnums=(0, 1, 2),
                                 **_fused_donate)

        # ---- lossy-link / push-sum consensus path ---------------------
        # Engine state: `_mass` is the push-sum mass vector (ones —
        # exactly 1.0 forever under a doubly-stochastic fault-free
        # schedule); `_link_buf` is the bounded staleness buffer, [D, W,
        # ...] per leaf — under correction='none' it holds the fleet's
        # last D broadcast snapshots (a delayed edge mixes against one),
        # under push-sum the IN-FLIGHT packets (value mass en route,
        # slot d arrives in d+1 rounds) with `_link_buf_mass` the
        # matching scalar mass — so node mass + in-flight mass is
        # conserved at exactly n every round, the invariant
        # tests/test_network.py pins.  All of it is checkpointed;
        # link-mode runs execute per-round (the stack of per-staleness
        # matrices is host data per round).
        self._mass: object = {}
        self._link_buf: object = {}
        self._link_buf_mass: object = {}
        if self._link_mode:
            D = self._delay_max
            buf_sharding = jax.sharding.NamedSharding(
                self.mesh,
                jax.sharding.PartitionSpec(None, worker_axes(self.mesh)))
            if self._push_sum:
                self._mass = jax.device_put(np.ones(w, np.float32))
                if D > 0:
                    self._link_buf = jax.device_put(
                        jax.tree.map(
                            lambda x: np.zeros((D,) + x.shape, x.dtype),
                            stacked), buf_sharding)
                    self._link_buf_mass = jax.device_put(
                        np.zeros((D, w), np.float32))
            elif D > 0:
                # History snapshots: every slot starts at the common
                # init (what each worker would have broadcast before
                # round 0), so early-round staleness is well defined
                # and a resumed run reloads the exact carried history.
                self._link_buf = jax.device_put(
                    jax.tree.map(
                        lambda x: np.broadcast_to(
                            x[None], (D,) + x.shape).copy(), stacked),
                    buf_sharding)

            push_sum, D_link = self._push_sum, self._delay_max
            num_w = w

            def _tree_add(a, b):
                return jax.tree.map(jnp.add, a, b)

            def link_round_core(params, mom, mass, buf, buf_mass, mats,
                                alive, limits, t, idx, bweight, train_x,
                                train_y, ex, ey, ew, vidx, vw, do_eval,
                                cmask=None):
                """One round through the lossy-link consensus: ``mats``
                is the [D+1, n, n] per-staleness stack for the round
                (slot 0 immediate; row-stochastic overall for
                correction='none', column-stochastic overall for
                push-sum).  Under push-sum ``params`` carries the
                NUMERATOR x; the de-biased estimate z = x/mass is what
                trains and evaluates, and z·mass is carried back."""
                x_send = (corrupt_update(params, cmask, corrupt_mode,
                                         corrupt_scale)
                          if has_corrupt else params)
                new_buf, new_buf_mass = buf, buf_mass
                if push_sum:
                    now_x = mix_dense(x_send, mats[0], mesh)
                    now_m = jnp.tensordot(mats[0], mass, axes=[[1], [0]],
                                          precision=MIX_PRECISION)
                    if D_link > 0:
                        now_x = _tree_add(
                            now_x, jax.tree.map(lambda b: b[0], buf))
                        now_m = now_m + buf_mass[0]
                        arr = [mix_dense(x_send, mats[d], mesh)
                               for d in range(1, D_link + 1)]
                        arr_m = jnp.stack(
                            [jnp.tensordot(mats[d], mass, axes=[[1], [0]],
                                           precision=MIX_PRECISION)
                             for d in range(1, D_link + 1)])

                        def slot_upd(b, *sends):
                            shifted = jnp.concatenate(
                                [b[1:], jnp.zeros_like(b[:1])], axis=0)
                            return shifted + jnp.stack(sends, axis=0)

                        new_buf = jax.tree.map(slot_upd, buf, *arr)
                        new_buf_mass = jnp.concatenate(
                            [buf_mass[1:], jnp.zeros_like(buf_mass[:1])],
                            axis=0) + arr_m
                    safe_m = jnp.maximum(now_m, 1e-12)

                    def debias(xl):
                        mm = safe_m.reshape(
                            (-1,) + (1,) * (xl.ndim - 1))
                        return (xl.astype(jnp.float32)
                                / mm).astype(xl.dtype)

                    mixed = jax.tree.map(debias, now_x)
                    mass_out = now_m
                else:
                    mixed = mix_dense(x_send, mats[0], mesh)
                    if D_link > 0:
                        for d in range(1, D_link + 1):
                            snap = jax.tree.map(lambda b, _d=d: b[_d - 1],
                                                buf)
                            mixed = _tree_add(
                                mixed, mix_dense(snap, mats[d], mesh))
                        new_buf = jax.tree.map(
                            lambda b, s: jnp.concatenate(
                                [s[None], b[:-1]], axis=0),
                            buf, x_send)
                    mass_out = mass
                screened = jnp.zeros(num_w, jnp.float32)
                evalm = jax.lax.cond(
                    do_eval, lambda: evaluator(mixed, ex, ey, ew),
                    zeros_eval)
                p_t, m_t, losses, accs, em = local_phase(
                    mixed, mom, idx, bweight, train_x, train_y, vidx, vw,
                    limits)
                if has_faults:
                    p_t = where_mask(alive, p_t, mixed)
                    m_t = where_mask(alive, m_t, mom)
                accs, counts = split_counts(accs)
                tl, ta = train_metrics(losses, accs, alive)
                # Diagnostics on the DE-BIASED estimates (pre-rebias):
                # under push-sum the carried numerators scale with mass,
                # and z = x/mass is the quantity that converges — the
                # same convention the end-of-run consensus gauge uses.
                diag = (round_diag(p_t, m_t, mixed, losses, alive)
                        if diag_on else None)
                if push_sum:
                    def rebias(zl):
                        mm = mass_out.reshape(
                            (-1,) + (1,) * (zl.ndim - 1))
                        return (zl.astype(jnp.float32)
                                * mm).astype(zl.dtype)

                    p_t = jax.tree.map(rebias, p_t)
                return (p_t, m_t, mass_out, new_buf, new_buf_mass,
                        pack_host_metrics(tl, ta, evalm, em, screened,
                                          diag, counts))

            self._link_round_fn = jax.jit(link_round_core,
                                          donate_argnums=(0, 1, 2, 3, 4))

            def link_block_fn(params, mom, mass, buf, buf_mass, mats,
                              alive, limits, ts, idx, bw, is_eval,
                              train_x, train_y, ex, ey, ew, vidx, vw,
                              cmasks=None):
                """k lossy-link rounds fused into one lax.scan: the
                push-sum mass + in-flight/staleness buffers (engine
                state) ride the CARRY, and the per-round [D+1, n, n]
                per-staleness matrix stacks ride the scan as one more
                stacked input ([k, D+1, n, n]) — exactly like the
                corrupt masks.  The body IS ``link_round_core``, so the
                per-round and blocked programs can never diverge."""

                def body(carry, xs):
                    p, m, ms, bf, bm = carry
                    if has_corrupt:
                        (mats_t, alive_t, lim_t, t_t, idx_t, bw_t, ev_t,
                         cm_t) = xs
                    else:
                        mats_t, alive_t, lim_t, t_t, idx_t, bw_t, ev_t = xs
                        cm_t = None
                    p, m, ms, bf, bm, packed = link_round_core(
                        p, m, ms, bf, bm, mats_t, alive_t, lim_t, t_t,
                        idx_t, bw_t, train_x, train_y, ex, ey, ew, vidx,
                        vw, ev_t, cm_t)
                    return (p, m, ms, bf, bm), packed

                xs = [mats, alive, limits, ts, idx, bw, is_eval]
                if has_corrupt:
                    xs.append(cmasks)
                (params, mom, mass, buf, buf_mass), packed = jax.lax.scan(
                    body, (params, mom, mass, buf, buf_mass), tuple(xs))
                return params, mom, mass, buf, buf_mass, packed

            self._link_block_fn = jax.jit(link_block_fn,
                                          donate_argnums=(0, 1, 2, 3, 4))

    # -- blocked staging: the stateful draw vs the pure build ----------
    def _draw_block(self, ts: list) -> dict:
        """The STATEFUL half of one block's host staging: the per-round
        fault/matrix/quarantine inputs.  Always runs on the main thread
        in block order — the 'gossip' matching-matrix RNG and the
        link-mode quarantine-expiry mutations must advance at exactly
        the sequence positions the unprefetched loop consumes them at
        (dopt.data.prefetch ordering contract)."""
        if self._fused_quar:
            statics = [self._round_inputs_static(t) for t in ts]
            return {"ts": ts,
                    "w_raws": [s[0] for s in statics],
                    "w_mats": np.stack([s[1] for s in statics]),
                    "alive": np.stack([s[2] for s in statics]),
                    "limits": np.stack([s[3] for s in statics]),
                    "cmasks": (np.stack([s[4] for s in statics])
                               if self._has_corrupt else None),
                    "frows": None}
        pairs = [self._round_inputs(t) for t in ts]
        meta = {"ts": ts,
                "w_raws": None,
                "w_mats": np.stack([(p[0][0] if self._async else p[0])
                                    for p in pairs]),
                "alive": np.stack([p[1] for p in pairs]),
                "limits": np.stack([p[2] for p in pairs]),
                "cmasks": (np.stack([p[3] for p in pairs])
                           if self._has_corrupt else None),
                "frows": [p[4] for p in pairs]}
        if self._async:
            meta["wdiags"] = np.stack([p[0][1] for p in pairs])
        return meta

    def _build_block(self, meta: dict) -> dict:
        """The PURE half of one block's host staging: the batch plans
        (the expensive O(W·S·B) host work) and their device staging.
        Touches no trainer state beyond stateless reads, so the
        prefetch stager may run it on its background thread."""
        ts = meta["ts"]
        block_sharding = jax.sharding.NamedSharding(
            self.mesh,
            jax.sharding.PartitionSpec(None, worker_axes(self.mesh)))
        plans = [self._round_plan(t) for t in ts]
        meta["idx"] = jax.device_put(np.stack([p.idx for p in plans]),
                                     block_sharding)
        meta["bw"] = jax.device_put(np.stack([p.weight for p in plans]),
                                    block_sharding)
        meta["is_eval"] = np.asarray(
            [(t % self.eval_every) == 0 for t in ts], dtype=bool)
        return meta

    def _blocked_path(self, block: int) -> RoundPath:
        """The path that fuses up to ``block`` rounds into one scan.

        EVERY gossip mode is blocked-eligible: clean/faulted runs fuse
        as before; link-mode runs (msg_drop/msg_delay/push-sum) scan
        with the mass + staleness buffers as carry and the per-round
        [D+1, n, n] matrix stacks as stacked inputs; fused-quarantine
        runs carry the streak/until state on device and the host
        REPLAYS the per-round ledger logic post-fetch (same rows, same
        order — the screened flags it needs only exist after the block
        lands)."""
        link, fused_quar = self._link_mode, self._fused_quar
        # The scan's final streak/until carry, held from ``commit`` to
        # ``record``'s check against the host replay.
        dev_quar: list = []

        def launch(payload):
            step_kw = ({"cmasks": jnp.asarray(payload["cmasks"])}
                       if self._has_corrupt else {})
            common = (payload["w_mats"], payload["alive"],
                      payload["limits"],
                      jnp.asarray(payload["ts"], jnp.int32),
                      payload["idx"], payload["bw"],
                      jnp.asarray(payload["is_eval"]), self._train_x,
                      self._train_y, *self._eval, *self._val)
            if link:
                return ("link_block_fn", self._link_block_fn,
                        (self.params, self.momentum, self._mass,
                         self._link_buf, self._link_buf_mass, *common),
                        step_kw)
            if fused_quar:
                step_kw.update(
                    streak=jnp.asarray(
                        self._screen_streak.astype(np.int32)),
                    until=jnp.asarray(
                        self._quarantine_until.astype(np.int32)))
            else:
                if self._async:
                    step_kw.update(prev=self._async_prev,
                                   wdiags=jnp.asarray(payload["wdiags"]))
                if self._fused_on:
                    step_kw["fbuf"] = self._fused_buf
                if self._codec_on:
                    step_kw["cres"] = self._comm_res
            return ("block_fn", self._block_fn,
                    (self.params, self.momentum, self.x_hat, *common),
                    step_kw)

        def commit(out):
            if fused_quar:
                dev_quar[:] = out[3:5]
                out = out[:3] + out[5:]
            return self._commit(out)

        def record(payload, packed):
            for j, t in enumerate(payload["ts"]):
                if fused_quar:
                    # Post-fetch ledger replay: host state is now
                    # current through round t-1's flags, so this
                    # regenerates exactly the per-round path's rows
                    # (and host-mirror mutations) for round t.
                    (_w, alive_j, _lim, _cm, rows_j,
                     quar_j) = self._round_inputs(
                         t, w_raw=payload["w_raws"][j])
                    alive_j = alive_j * (1.0 - quar_j)
                else:
                    alive_j, rows_j = payload["alive"][j], payload["frows"][j]
                self._record_round(t, packed[j], alive_j, rows_j,
                                   payload["is_eval"][j])
            if fused_quar:
                # The host replay and the device carry apply the same
                # integer rule to the same flags — drift here means a
                # real bug, caught loudly rather than as silent trace
                # divergence.
                dev_streak, dev_until = dev_quar
                if not (np.array_equal(np.asarray(dev_streak),
                                       self._screen_streak.astype(np.int32))
                        and np.array_equal(
                            np.asarray(dev_until),
                            self._quarantine_until.astype(np.int32))):
                    raise RuntimeError(
                        "fused-quarantine host replay diverged from the "
                        "device scan carry")

        return RoundPath(draw=self._draw_block, build=self._build_block,
                         launch=launch, commit=commit, record=record,
                         block=block, prefetch=self._prefetch)

    def _round_path(self) -> RoundPath:
        """The per-round path: the payload is ``_round_dispatch``'s
        tuple, the ONE builder ``lower_round`` also consumes.  It reads
        the carried state, so nothing of it can be staged ahead."""

        def record(dispatch, packed):
            alive, quar, frows, do_eval = dispatch[4:]
            if self._fused_quar:
                alive = alive * (1.0 - quar)
            self._record_round(self.round, packed, alive, frows, do_eval)

        return RoundPath(draw=lambda ts: self._round_dispatch(ts[0]),
                         launch=lambda dispatch: dispatch[:4],
                         commit=self._commit, record=record)

    def _commit(self, out):
        """Assign the carried state from a round program's result — one
        round's or a block's, the tuple is the same — and return the
        packed metrics, always its last element."""
        *state, packed = out
        if self._link_mode:
            (self.params, self.momentum, self._mass, self._link_buf,
             self._link_buf_mass) = state
        elif self._async:
            (self.params, self.momentum, self.x_hat,
             self._async_prev) = state
        elif self._fused_on:
            (self.params, self.momentum, self.x_hat,
             self._fused_buf) = state
        elif self._codec_on:
            (self.params, self.momentum, self.x_hat,
             self._comm_res) = state
        else:
            self.params, self.momentum, self.x_hat = state
        return packed

    def _record_round(self, t: int, vec: np.ndarray, alive, frows: list,
                      do_eval) -> None:
        """Round ``t``'s host record from its fetched metrics vector:
        screen feedback into the ledger rows and quarantine streaks
        (``alive`` is the round's effective alive mask), the history
        row, the client rows, the telemetry bundle; advances
        ``self.round``."""
        (tl, ta, acc, lm, scr, em, diag,
         counts) = self._unpack_host_metrics(vec)
        if self._robust_active:
            self._apply_screen_feedback(t, alive, scr, frows)
        self.history.faults.extend(frows)
        row = {
            "round": t,
            "avg_train_loss": tl,
            "avg_train_acc": ta,
            **counts,
        }
        if do_eval:
            row["avg_test_acc"] = acc
            row["avg_test_loss"] = lm
        self.history.append(**row)
        if self._holdout:
            self._append_client_rows(t, em)
        self._round_telemetry(t, frows, diag)
        self.round += 1

    # ------------------------------------------------------------------
    def _unpack_host_metrics(self, vec: np.ndarray):
        """Inverse of the round step's ``pack_host_metrics``: one fetched
        f32 vector → (train_loss, train_acc, mean_test_acc,
        mean_test_loss, [W] screened flags (robust runs; else None), em
        dict of [W, E] arrays or {}, [6] diagnostics block
        (diagnostics runs; else None), {name: value} of a sequence
        model's routing counts or {})."""
        tl, ta, acc, lm = (float(vec[0]), float(vec[1]), float(vec[2]),
                           float(vec[3]))
        off = 4
        scr = None
        if self._robust_active:
            scr = vec[off:off + self.num_workers]
            off += self.num_workers
        em: dict[str, np.ndarray] = {}
        if self._holdout:
            w, e = self.num_workers, self.cfg.gossip.local_ep
            n = w * e
            body = vec[off:]
            for i, k in enumerate(("train_loss", "train_acc", "val_acc",
                                   "val_loss")):
                em[k] = body[i * n:(i + 1) * n].reshape(w, e)
            off += 4 * n
        counts = {k: float(vec[off + i])
                  for i, k in enumerate(self.counters)}
        diag = vec[-len(self._diag_keys):] if self._diag else None
        return tl, ta, acc, lm, scr, em, diag, counts

    def _append_client_rows(self, t: int, em: dict) -> None:
        """Per-epoch per-worker history rows (P2 Client.history schema,
        clients.py:52-57: {iter, train_loss, train_acc, val_acc,
        val_loss} with val_loss in P2's mean-per-batch flavour), one row
        per (worker, epoch)."""
        tl, ta = em["train_loss"], em["train_acc"]
        va, vl = em["val_acc"], em["val_loss"]
        for i in range(self.num_workers):
            for e in range(tl.shape[1]):
                self.client_history.append(
                    round=t, iter=e, worker=i,
                    train_loss=float(tl[i, e]), train_acc=float(ta[i, e]),
                    val_acc=float(va[i, e]), val_loss=float(vl[i, e]),
                )

    def _consensus_operands(self):
        """The de-biased estimates (push-sum runs measure the ratio
        estimates — the quantity that actually converges), centred on
        their own mean."""
        return (self._debiased_params(),)

    def _matrix_for_round(self, t: int) -> np.ndarray:
        g = self.cfg.gossip
        if g.algorithm == "gossip":
            return random_matching_matrix(self.num_workers, self._matching_rng)
        if self.mixing is not None:
            return self.mixing.for_round(t)
        return np.eye(self.num_workers)

    def _round_inputs_static(self, t: int):
        """Quarantine-INDEPENDENT per-round inputs for the fused-
        quarantine blocked path: (raw matrix draw, partition-cut f32
        matrix, alive mask from crash/churn only, straggler limits,
        raw corrupt mask).  Draws the round's matrix — the only
        stateful draw — and touches NO quarantine state and emits NO
        ledger rows; the blocked loop replays ``_round_inputs(t,
        w_raw=...)`` post-fetch for the rows + host-mirror updates,
        once the block's screened flags are back."""
        w_raw = self._matrix_for_round(t)
        rf = self.faults.for_round(t)
        alive = (~rf.crashed).astype(np.float32)
        if self.faults.has_churn:
            away = self.faults.away_for_round(t)
            alive = alive * (~away).astype(np.float32)
        limits = FaultPlan.limits_for(rf, self._straggle_units)
        w_t = w_raw
        if rf.partition is not None:
            w_t = repair_for_partition(w_t, rf.partition)
        cmask = np.zeros(self.num_workers, np.float32)
        if self._has_corrupt and rf.corrupt is not None:
            cmask = (rf.corrupt & (alive > 0)).astype(np.float32)
        return w_raw, w_t.astype(np.float32), alive, limits, cmask

    def _round_inputs(
            self, t: int, w_raw: np.ndarray | None = None
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, list,
               np.ndarray]:
        """(mixing argument, alive mask, straggler limits, corrupt mask,
        ledger rows, quarantine mask) for round t, with the matrix
        repaired for any failed or quarantined workers.

        The mixing argument is the [n, n] matrix on the dense path or
        its [k, n] circulant coefficient table on the shift/ppermute
        path (same math: ``coeffs_for_matrix`` raises if the matrix
        ever leaves the compiled shift set, so the two paths can never
        silently diverge).  Faults are drawn statelessly per round
        (dopt.faults.FaultPlan) and ledger rows are RETURNED (not
        appended) so both execution paths interleave them with the
        device-side screened rows in the identical order — per-round,
        blocked, and killed-and-resumed execution log the same trace.

        Under FUSED quarantine (dense robust path) the contract shifts:
        the returned matrix is NOT dropout-repaired and ``alive``
        excludes crash/churn only — the device folds the quarantine
        mask in and repairs (``effective_inputs``), identically on the
        per-round and blocked paths.  ``w_raw`` lets the blocked replay
        reuse the plan-time matrix draw (the matching RNG is stateful).
        """
        rows: list[dict] = []
        w_t = self._matrix_for_round(t) if w_raw is None else w_raw
        rf = self.faults.for_round(t)
        alive = (~rf.crashed).astype(np.float32)
        away = self.faults.away_for_round(t)
        if self.faults.has_churn:
            rows.extend(churn_ledger_rows(self.faults, t, away))
            alive = alive * (~away).astype(np.float32)
        quar = np.zeros(self.num_workers, np.float32)
        if self._quarantine_on:
            expired = ((self._quarantine_until != 0)
                       & (t >= self._quarantine_until))
            for i in np.nonzero(expired)[0]:
                rows.append({"round": int(t), "worker": int(i),
                             "kind": "quarantine", "action": "readmitted"})
                self._quarantine_until[i] = 0
                self._screen_streak[i] = 0
            quarantined = self._quarantine_until > t
            quar = quarantined.astype(np.float32)
            if quarantined.any() and not self._fused_quar:
                # Quarantine rides the existing alive machinery: the
                # matrix is repaired around the worker (neighbors stop
                # listening) and its lane freezes for the span.  On the
                # fused path this fold happens ON DEVICE instead.
                alive = alive * (~quarantined).astype(np.float32)
        units = self._straggle_units
        limits = FaultPlan.limits_for(rf, units)
        if rf.partition is not None:
            # Cut cross-group edges FIRST, then repair for crashes: a
            # crashed worker is down regardless of which side it is on.
            w_t = repair_for_partition(w_t, rf.partition)
            for i, gid in enumerate(rf.partition):
                rows.append({"round": int(t), "worker": int(i),
                             "kind": "partition",
                             "action": f"cut_to_group_{int(gid)}"})
        if alive.min() < 1.0 and not self._fused_quar:
            w_t = repair_for_dropout(w_t, alive)
        for i in np.nonzero(rf.crashed)[0]:
            rows.append({"round": int(t), "worker": int(i), "kind": "crash",
                         "action": "skipped_round"})
        for i in np.nonzero(rf.straggler)[0]:
            rows.append({"round": int(t), "worker": int(i),
                         "kind": "straggler",
                         "action": f"truncated_to_{int(limits[i])}_of_{units}"})
        cmask = np.zeros(self.num_workers, np.float32)
        if self._has_corrupt and rf.corrupt is not None:
            # A down (or quarantined) worker sends nothing to corrupt.
            # Fused path: the returned cmask keeps quarantined liars
            # (the device mutes them), the LEDGER excludes them — same
            # effective set either way.
            liars = rf.corrupt & (alive > 0)
            cmask = liars.astype(np.float32)
            row_liars = liars & (quar <= 0) if self._fused_quar else liars
            mode = self.cfg.faults.corrupt_mode
            for i in np.nonzero(row_liars)[0]:
                rows.append({"round": int(t), "worker": int(i),
                             "kind": "corrupt",
                             "action": f"injected_{mode}"})
        if self._link_mode:
            # Per-edge link faults + the per-staleness matrix stack.
            # Drops/delays apply to the surviving off-diagonal edges of
            # the (crash/partition/churn-)repaired matrix; push-sum gets
            # the mass-conserving column-stochastic effective matrix,
            # plain gossip the row-renormalised (biased) one.
            from dopt.topology import (push_sum_link_matrix,
                                       repair_for_link_drop,
                                       split_by_delay)

            keep, delay = self.faults.link_for_round(t)
            if self._has_link:
                edges = (w_t * (1.0 - np.eye(self.num_workers))) > 0.0
                for i, j in zip(*np.nonzero(edges & ~keep)):
                    rows.append({"round": int(t), "worker": int(i),
                                 "kind": "msg_drop",
                                 "action": f"dropped_from_{int(j)}"})
                for i, j in zip(*np.nonzero(edges & keep & (delay > 0))):
                    rows.append({
                        "round": int(t), "worker": int(i),
                        "kind": "msg_delay",
                        "action": f"delayed_from_{int(j)}_by_"
                                  f"{int(delay[i, j])}"})
            m_eff = (push_sum_link_matrix(w_t, keep) if self._push_sum
                     else repair_for_link_drop(w_t, keep))
            mats = split_by_delay(m_eff, delay, self._delay_max)
            return mats, alive, limits, cmask, rows, quar
        if self._async:
            # Diag/off-diag split AFTER every repair above: a departed
            # (crashed/churned/partition-isolated) lane's identity row
            # becomes diag=1 / off-diag=0 — a pure local step with no
            # stale read from, or into, the dead lane.  The off-diag
            # support is a subset of the full support, so the compiled
            # shift set always covers it.
            wdiag = np.diag(w_t).astype(np.float32)
            w_off = (w_t * (1.0 - np.eye(self.num_workers))).astype(
                np.float32)
            arg = (coeffs_for_matrix(w_off, self._shift_ids)
                   if self._shift_ids is not None else w_off)
            return (arg, wdiag), alive, limits, cmask, rows, quar
        if self._shift_ids is not None:
            return (coeffs_for_matrix(w_t, self._shift_ids), alive, limits,
                    cmask, rows, quar)
        return w_t.astype(np.float32), alive, limits, cmask, rows, quar

    def _plan_matrix_for_round(self, t: int) -> np.ndarray:
        return self.faults.plan_matrix_for(t, self._train_matrix)

    def _round_plan(self, t: int):
        """Round t's batch plan: the classic per-lane plan, or — in
        population mode — the sampled cohort bound onto the lanes (lane
        i trains client c_i's shard under client c_i's batch stream;
        sampling is stateless per (seed, round), so blocked and resumed
        runs bind identical cohorts).  Appends the round's ``cohort``
        audit row and updates the registry's participation counters as
        a side effect."""
        cfg, g = self.cfg, self.cfg.gossip
        if self._registry is None:
            return make_batch_plan(
                self._plan_matrix_for_round(t), batch_size=g.local_bs,
                local_ep=g.local_ep, seed=cfg.seed, round_idx=t,
                impl=cfg.data.plan_impl)
        reg = self._registry
        cohort = reg.sample_cohort(t)
        binding = reg.bind(t, cohort, cohort)
        ids = binding.lane_ids[0]
        reg.record_participation(t, binding.survivors)
        self.history.faults.append(binding.ledger_row(reg.clients))
        return make_batch_plan(
            self._train_matrix, batch_size=g.local_bs,
            local_ep=g.local_ep, seed=cfg.seed, round_idx=t,
            impl=cfg.data.plan_impl, workers=ids,
            rows=reg.shard_of[ids])

    def _apply_screen_feedback(self, t: int, alive, flags,
                               rows: list) -> None:
        """Fold the device step's screened-sender flags (non-finite or
        majority-clipped broadcasts) into the ledger and the quarantine
        streaks: K consecutive screened rounds quarantine the worker for
        ``quarantine_rounds``; one clean alive round resets the
        streak."""
        for i in range(self.num_workers):
            if float(flags[i]) > 0.5:
                self._screen_streak[i] += 1
                rows.append({"round": int(t), "worker": i,
                             "kind": "corrupt", "action": "screened"})
                if (self._quarantine_on and self._screen_streak[i]
                        >= self._quarantine_after):
                    until = int(t) + 1 + self._quarantine_rounds
                    self._quarantine_until[i] = until
                    self._screen_streak[i] = 0
                    rows.append({"round": int(t), "worker": i,
                                 "kind": "quarantine",
                                 "action": f"quarantined_until_{until}"})
            elif float(alive[i]) > 0:
                self._screen_streak[i] = 0

    def run(self, rounds: int | None = None, eps: int | None = None,
            block: int | None = None, checkpoint_every: int = 0,
            checkpoint_path=None) -> History:
        """Train; mirrors ``Simulator.run(rounds)`` / ``FedLCon.run(rounds, eps)``.

        ``block`` (default ``cfg.gossip.block_rounds``) > 1 fuses that
        many rounds into one jit dispatch (``_blocked_path``) — same
        math, same phase order, same eval cadence; only the host/device
        round-trip count changes.

        ``checkpoint_every=K`` (with ``checkpoint_path``) auto-saves a
        full checkpoint every K rounds; a run killed at any point and
        resumed from the latest checkpoint is bit-identical to a
        continuous run (stateless fault/batch streams + persisted host
        RNG state)."""
        g = self.cfg.gossip
        rounds = g.rounds if rounds is None else rounds
        if eps is not None and eps != g.eps and g.algorithm == "fedlcon":
            raise ValueError("set eps in GossipConfig (static for compilation)")
        if checkpoint_every and checkpoint_path is None:
            raise ValueError("checkpoint_every requires checkpoint_path")
        block = g.block_rounds if block is None else block
        path = self._blocked_path(block) if block > 1 else self._round_path()
        return self._run_loop(path, rounds, checkpoint_every,
                              checkpoint_path)

    def _round_dispatch(self, t: int):
        """Round ``t``'s device dispatch, fully built: ``(fn_name,
        step_fn, args, kwargs, alive, quar, frows, do_eval)``.  The ONE
        builder both the per-round ``run`` loop and ``lower_round``
        consume — which is what makes the program-fingerprint gate
        (``dopt.analysis.fingerprint``) pin the program the real loop
        actually dispatches, with no mirror to drift.  Advances the
        same stateful host draws (matching RNG, ledger rows) the run
        loop would."""
        w_t, alive, limits, cmask, frows, quar = self._round_inputs(t)
        plan = self._round_plan(t)
        idx = jax.device_put(plan.idx, self._sharding)
        bweight = jax.device_put(plan.weight, self._sharding)
        do_eval = (t % self.eval_every) == 0
        step_kw = ({"cmask": jnp.asarray(cmask)}
                   if self._has_corrupt else {})
        if self._fused_quar:
            # The quarantine fold + matrix repair happen ON DEVICE
            # (effective_inputs), identically to the blocked path.
            step_kw["quar"] = jnp.asarray(quar)
        if self._link_mode:
            args = (self.params, self.momentum, self._mass,
                    self._link_buf, self._link_buf_mass,
                    jnp.asarray(w_t), alive, limits,
                    jnp.asarray(t, jnp.int32), idx, bweight,
                    self._train_x, self._train_y, *self._eval,
                    *self._val, do_eval)
            return ("link_round_fn", self._link_round_fn, args, step_kw,
                    alive, quar, frows, do_eval)
        if self._async:
            w_t, wdiag = w_t
            step_kw["prev"] = self._async_prev
            step_kw["wdiag"] = jnp.asarray(wdiag)
        if self._fused_on:
            step_kw["fbuf"] = self._fused_buf
        if self._codec_on:
            step_kw["cres"] = self._comm_res
        args = (self.params, self.momentum, self.x_hat, w_t, alive,
                limits, jnp.asarray(t, jnp.int32), idx, bweight,
                self._train_x, self._train_y, *self._eval, *self._val,
                do_eval)
        return ("round_fn", self._round_fn, args, step_kw, alive, quar,
                frows, do_eval)

    def lower_round(self, t: int | None = None):
        """Lower (without executing) round ``t``'s device step exactly
        as the per-round ``run`` loop would dispatch it — same
        ``_round_dispatch`` builder, so the two cannot diverge — and
        return ``(fn_name, jax.stages.Lowered)``.  The program-
        fingerprint hook; call it on a FRESHLY CONSTRUCTED trainer only
        (building the inputs consumes the run loop's stateful draws)."""
        t = self.round if t is None else t
        fn_name, step_fn, args, step_kw, *_ = self._round_dispatch(t)
        return fn_name, step_fn.lower(*args, **step_kw)

    # ------------------------------------------------------------------
    def _save(self, path) -> None:
        """Full training state: params, momentum, round, history, AND
        host RNG state (the matching RNG is stateful — a resumed
        'gossip' run must not replay round-0 matchings)."""
        from dopt.utils.checkpoint import save_checkpoint

        arrays = {"params": self.params, "momentum": self.momentum}
        if self.cfg.gossip.algorithm == "choco":
            arrays["x_hat"] = self.x_hat
        if self._async:
            # The staleness-1 buffer is carried engine state: without
            # it a resumed async run would mix round t against the
            # wrong previous-round snapshot.
            arrays["async_prev"] = self._async_prev
        if self._fused_on:
            # The displacement buffer is carried engine state — and the
            # carried "params" are the POST-MIX q, not the post-local
            # endpoint — so a fused resume needs both trees to contract
            # round t exactly as the unkilled run would.
            arrays["fused_buf"] = self._fused_buf
        if self._codec_on:
            # The per-bucket error-feedback residual is carried engine
            # state: a resumed codec run must fold back exactly the
            # quantization error the unkilled run would have.
            arrays["comm_residual"] = {
                f"b{i}": r for i, r in enumerate(self._comm_res)}
        if self._link_mode:
            # Push-sum mass and the staleness buffers are carried engine
            # state: without them a resumed lossy-link run would replay
            # round t against the wrong in-flight/history snapshots.
            if self._push_sum:
                arrays["push_mass"] = {"mass": self._mass}
            if self._delay_max > 0:
                arrays["link_buf"] = self._link_buf
                if self._push_sum:
                    arrays["link_buf_mass"] = {"mass": self._link_buf_mass}
        meta = {"round": self.round, "name": self.cfg.name,
                "algorithm": self.cfg.gossip.algorithm,
                "history": self.history.rows,
                "client_history": self.client_history.rows,
                "fault_ledger": self.history.faults,
                "screen_streak": self._screen_streak.tolist(),
                "quarantine_until": self._quarantine_until.tolist(),
                "matching_rng_state": self._matching_rng.bit_generator.state}
        if self._registry is not None:
            meta["population_registry"] = self._registry.state_dict()
        save_checkpoint(path, arrays=arrays, meta=meta,
                        write=self.checkpoint_writer)

    def restore(self, path) -> None:
        """Resume from a checkpoint written by ``save`` (same config)."""
        from dopt.utils.checkpoint import load_checkpoint

        arrays, meta = load_checkpoint(path)
        if meta.get("algorithm") != self.cfg.gossip.algorithm:
            raise ValueError(
                f"checkpoint is for algorithm {meta.get('algorithm')!r}, "
                f"trainer runs {self.cfg.gossip.algorithm!r}"
            )
        self.params = shard_worker_tree(arrays["params"], self.mesh)
        self.momentum = shard_worker_tree(arrays["momentum"], self.mesh)
        if self.cfg.gossip.algorithm == "choco":
            if "x_hat" not in arrays:
                raise ValueError(
                    "choco trainer requires its public-copy state "
                    "('x_hat') in the checkpoint")
            self.x_hat = shard_worker_tree(arrays["x_hat"], self.mesh)
        if self._async:
            if "async_prev" not in arrays:
                raise ValueError(
                    "mixing='async' trainer requires its previous-round "
                    "state ('async_prev') in the checkpoint")
            self._async_prev = shard_worker_tree(arrays["async_prev"],
                                                 self.mesh)
        if self._fused_on:
            if "fused_buf" not in arrays:
                raise ValueError(
                    "fused_update='on' trainer requires its displacement "
                    "buffer ('fused_buf') in the checkpoint — this "
                    "checkpoint is from a fused_update='off' run, whose "
                    "carried params are the post-local endpoint, not "
                    "the (post-mix, displacement) pair")
            self._fused_buf = shard_worker_tree(arrays["fused_buf"],
                                                self.mesh)
        elif "fused_buf" in arrays:
            raise ValueError(
                "checkpoint carries a fused displacement buffer "
                "('fused_buf') but this trainer runs fused_update='off' "
                "— the checkpoint's 'params' are the post-mix state q, "
                "not the post-local endpoint; restore with "
                "fused_update='on'")
        if self._codec_on:
            if "comm_residual" not in arrays:
                raise ValueError(
                    "comm.codec trainer requires its per-bucket "
                    "error-feedback residual ('comm_residual') in the "
                    "checkpoint — this checkpoint is from an "
                    "uncompressed run, whose rounds never accumulated "
                    "a quantization error to feed back")
            res = arrays["comm_residual"]
            self._comm_res = shard_worker_tree(
                tuple(res[f"b{i}"] for i in range(len(res))), self.mesh)
        elif "comm_residual" in arrays:
            raise ValueError(
                "checkpoint carries a comm error-feedback residual "
                "('comm_residual') but this trainer runs without the "
                "bucket codec — the residual's pending correction "
                "would be silently dropped; restore with the same "
                "CommConfig codec armed")
        if self._link_mode:
            if self._push_sum:
                if "push_mass" not in arrays:
                    raise ValueError(
                        "push-sum trainer requires its mass vector "
                        "('push_mass') in the checkpoint")
                self._mass = jnp.asarray(arrays["push_mass"]["mass"])
            if self._delay_max > 0:
                if "link_buf" not in arrays:
                    raise ValueError(
                        "link-delay trainer requires its staleness "
                        "buffer ('link_buf') in the checkpoint")
                # Restore with the constructor's placement ([D, W, ...]
                # sharded over the worker axis) so a resumed run feeds
                # the compiled round fn identically-sharded inputs —
                # a bare asarray would leave D full-model snapshots
                # replicated per device.
                buf_sharding = jax.sharding.NamedSharding(
                    self.mesh,
                    jax.sharding.PartitionSpec(None,
                                               worker_axes(self.mesh)))
                self._link_buf = jax.device_put(arrays["link_buf"],
                                                buf_sharding)
                if self._push_sum:
                    if "link_buf_mass" not in arrays:
                        raise ValueError(
                            "push-sum + delay trainer requires the "
                            "in-flight mass buffer ('link_buf_mass') in "
                            "the checkpoint")
                    self._link_buf_mass = jnp.asarray(
                        arrays["link_buf_mass"]["mass"])
        self.round = int(meta["round"])
        self.history.rows = list(meta.get("history", []))
        self.history.faults = list(meta.get("fault_ledger", []))
        self.client_history.rows = list(meta.get("client_history", []))
        w = self.num_workers
        self._screen_streak = np.asarray(
            meta.get("screen_streak", [0] * w), np.int64)
        self._quarantine_until = np.asarray(
            meta.get("quarantine_until", [0] * w), np.int64)
        if meta.get("matching_rng_state"):
            self._matching_rng.bit_generator.state = meta["matching_rng_state"]
        if self._registry is not None:
            state = meta.get("population_registry")
            if state is None:
                raise ValueError(
                    "population-mode trainer requires its registry state "
                    "('population_registry') in the checkpoint — this "
                    "checkpoint is from a lane-engine run")
            self._registry.load_state(state)
        if meta.get("dropout_rng_state"):
            # Checkpoint from before dropout joined FaultPlan, whose
            # draws are stateless per round: the resumed run's failure
            # sequence is deterministic but NOT the one the stateful
            # stream would have produced.
            import warnings

            warnings.warn(
                "checkpoint carries the legacy stateful dropout RNG; "
                "dropout faults now draw statelessly per round "
                "(dopt.faults.FaultPlan), so this run's failure "
                "sequence will differ from the original pre-upgrade "
                "run", stacklevel=2)

    def _debiased_params(self):
        """Device-resident per-worker parameter estimates: the carried
        params, or — under ``correction='push_sum'``, where the carried
        state is the NUMERATOR — the de-biased ratio estimates
        params/mass (the quantity that converges to the true average
        under lossy links).  The divide runs on device so callers never
        pay a host round-trip for it."""
        if self._fused_on:
            # Fused carry holds the POST-MIX state q and the pending
            # displacement; the round's semantic endpoint — what the
            # default path carries as params — is q − fbuf.
            return jax.tree.map(lambda a, b: a - b, self.params,
                                self._fused_buf)
        if not self._push_sum:
            return self.params
        mass = self._mass

        def debias(x):
            mm = jnp.maximum(mass, 1e-12).reshape(
                (-1,) + (1,) * (x.ndim - 1))
            return (x.astype(jnp.float32) / mm).astype(x.dtype)

        return jax.tree.map(debias, self.params)

    def worker_params(self):
        """Host copy of ``_debiased_params`` ([W, ...] pytree)."""
        return jax.device_get(self._debiased_params())

    # Convenience: per-worker eval of the current state (reuses the
    # round step's evaluator — same wrapping, same jit cache).
    def evaluate(self) -> dict[str, np.ndarray]:
        """Reference-semantics eval: EVERY worker on the FULL test set,
        regardless of ``eval_mode`` (the sharded mode only changes the
        in-training per-round metric).  Push-sum runs evaluate the
        de-biased estimates."""
        if self._eval_full is None:
            ex, ey, ew = eval_batches(self.dataset.test_x,
                                      self.dataset.test_y,
                                      batch_size=max(self.cfg.gossip.local_bs,
                                                     256))
            self._eval_full = (jnp.asarray(ex), jnp.asarray(ey),
                               jnp.asarray(ew))
        out = jax.jit(self._full_evaluator)(self._debiased_params(),
                                            *self._eval_full)
        return {k: np.asarray(v) for k, v in out.items()}
