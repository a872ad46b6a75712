"""Server-coordinated federated training (the reference's project 1).

Re-creates ``Server``/``FedAvg_Server``/``FedProx_Server``/``FedAdmm_Server``
(``Decentralized Optimization/src/servers.py``) on the stacked-worker
mesh engine:

* Client sampling (``np.random.choice``, servers.py:57) becomes a 0/1
  participation mask over the worker axis; sampled workers load the
  global model theta, train locally, and theta is re-formed as a masked
  uniform average (``average_weights``, servers.py:42-48 →
  ``masked_average`` = one reduce over the worker axis).
* Unsampled workers keep their stale params/momentum — faithful to the
  reference, where each client's optimizer (and its momentum buffer)
  lives for the whole experiment and only sampled clients step.
* FedProx / FedADMM are gradient edits inside the local scan; the ADMM
  duals are a worker-stacked (sharded) pytree with dual ascent after the
  local epochs (clients.py:141-144), only for sampled workers.
* Two execution paths, same math: the full-width path trains ALL N
  lanes and mask-discards the unsampled results (static shapes, right
  for sharded meshes where lanes are parallel hardware anyway), and the
  compact-sampling fast path (``FederatedConfig.compact``, auto-on for
  single-device meshes) gathers the m sampled workers into [m, ...]
  lanes, trains only those, and scatters back — an ~N/m compute saving
  at frac = m/N.

History schema is P1's: round, test_acc, test_loss (global model on the
test set), train_loss, train_acc (mean over ALL clients of their own
model on their own train split — ``avg_trainig_calculator``,
servers.py:85-93).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from dopt.config import ExperimentConfig
from dopt.data import (eval_batches, load_dataset, make_batch_plan,
                       partition, stacked_eval_batches)
from dopt.engine.local import (_stacked_eval_scan, flat_input_apply,
                               flat_input_stacked_apply, gather_rows,
                               make_evaluator,
                               make_stacked_local_update,
                               make_stacked_local_update_epochs,
                               prepare_holdout, validate_optimizer)
from dopt.engine.loop import HostLoop, RoundPath
from dopt.faults import FaultPlan, churn_ledger_rows, corrupt_update
from dopt.models import build_model, count_params
from dopt.optim import admm_dual_ascent, scaffold_control_update
from dopt.parallel.collectives import (broadcast_to_workers,
                                        make_update_shard_spec,
                                        masked_average,
                                        masked_average_scatter,
                                        where_mask as _where_mask)
from dopt.robust import (clip_to_ball, finite_lane_mask, global_norm_f32,
                         lane_sq_norms, make_aggregator,
                         masked_mean, validate_robust_config)
from dopt.parallel.mesh import (make_worker_mesh, shard_worker_tree,
                                worker_axes, worker_sharding)
from dopt.utils.metrics import History
from dopt.utils.profiling import PhaseTimers
from dopt.utils.prng import host_rng


class FederatedTrainer(HostLoop):
    """FedAvg / FedProx / FedADMM / SCAFFOLD with partial participation.

    SCAFFOLD exists in the reference only as commented-out dead code
    (``Decentralized Optimization/src/clients.py:146-170``); here it is
    the real algorithm: client control variates c_i are a worker-stacked
    sharded pytree (like the ADMM duals), the server control variate c is
    replicated, the local gradient edit is ``g − c_i + c`` and the
    option-II refresh ``c_i⁺ = c_i − c + (theta − y_i)/(K·lr)`` runs after
    the local epochs for sampled workers only.
    """

    engine_kind = "federated"

    def __init__(self, cfg: ExperimentConfig, *, eval_train: bool = True,
                 membership=None):
        if cfg.federated is None:
            raise ValueError("cfg.federated must be set for FederatedTrainer")
        if membership is not None and cfg.population is not None:
            raise ValueError(
                "the serve membership overlay does not compose with the "
                "client population registry (cohort sampling already "
                "models client join/leave; a lane-level overlay would "
                "silently fight the registry's shard assignment) — drop "
                "one of the two")
        f = cfg.federated
        if f.algorithm not in ("fedavg", "fedprox", "fedadmm", "scaffold"):
            raise ValueError(f"unknown federated algorithm {f.algorithm!r}")
        from dopt.engine.gossip import _reject_sequence_model

        _reject_sequence_model(cfg)
        validate_optimizer(cfg)
        self.model = build_model(
            cfg.model.model, num_classes=cfg.model.num_classes,
            faithful=cfg.model.faithful, dtype=cfg.model.compute_dtype,
            stage_sizes=cfg.model.stage_sizes, decoder=cfg.model.decoder,
        )
        if hasattr(self.model, "loss"):
            raise ValueError(
                f"model={cfg.model.model!r} brings its own token loss (a "
                "sequence objective) and trains through the gossip engine "
                "(GossipTrainer); the federated step cores and evaluators "
                "still assume one label a row (ROADMAP R1)")
        self.cfg = cfg
        self.eval_train = eval_train
        self.round = 0
        self.history = History(cfg.name)
        # Per-epoch per-client rows (only filled when the local holdout
        # is on): the reference's Client.history (P1 clients.py:50:
        # {global_round, epoch, train_loss, train_acc, val_acc,
        # val_loss}), plus a 'worker' column; sampled clients only, like
        # the reference (only sampled clients run update_weights).
        self.client_history = History(cfg.name + "-clients")
        self.timers = PhaseTimers()
        # Telemetry (dopt.obs): None (default) = the exact pre-telemetry
        # host loop; set via dopt.obs.attach.  Every emission site below
        # is python-gated on it and lives on the HOST side of the
        # post-fetch boundary, so the compiled device programs are
        # independent of it either way.
        self.telemetry = None
        # Serve-mode hook (dopt.serve): see GossipTrainer.
        self.checkpoint_writer = True

        w = cfg.data.num_users
        self.num_workers = w
        self.mesh = make_worker_mesh(w, cfg.mesh_devices, cfg.mesh_hosts)
        self._sharding = worker_sharding(self.mesh)

        # Fault injection (dopt.faults.FaultPlan): crashes, stragglers
        # and partitions are drawn statelessly per round on the HOST and
        # folded into the participation mask / lane gates — a crashed
        # (or partition-unreachable, or deadline-dropped) sampled client
        # contributes nothing to the aggregate and keeps its stale
        # state; it rejoins by reloading theta when next sampled.  The
        # device programs only ever see masks/gates/limits as data, so
        # the fault-free compiled program is exactly the pre-fault one.
        self.faults = FaultPlan(w, cfg.faults, seed=cfg.seed,
                                membership=membership)
        has_faults = self.faults.active
        may_straggle = (self.faults.may_straggle
                        and cfg.faults.straggler_policy == "partial")
        self._may_straggle = may_straggle

        # Byzantine threat model (dopt.robust): corrupt-update injection
        # rides the same stateless per-round fault streams; the defense
        # is the aggregation layer.  The non-finite screen is ALWAYS on
        # (a NaN/Inf update is treated as failed for the round instead
        # of silently poisoning theta); robust aggregators / clipping /
        # quarantine activate only when configured, and with
        # aggregator='mean' the exact pre-robust masked-average call is
        # kept so clean runs stay bit-identical.
        has_corrupt = self.faults.has_corrupt
        self._has_corrupt = has_corrupt
        corrupt_mode = cfg.faults.corrupt_mode if has_corrupt else "nan"
        corrupt_scale = cfg.faults.corrupt_scale if has_corrupt else 1.0
        rcfg = cfg.robust
        if rcfg is not None:
            validate_robust_config(rcfg)
        aggregator = rcfg.aggregator if rcfg is not None else "mean"
        clip_radius = rcfg.clip_radius if rcfg is not None else 0.0
        if aggregator != "mean" and f.comm_dtype:
            raise ValueError(
                "comm_dtype wire compression only applies to the masked-"
                f"mean reduce; aggregator={aggregator!r} is a full-"
                "precision robust statistic — drop one of the two")
        agg_robust = (make_aggregator(aggregator, trim_frac=rcfg.trim_frac,
                                      krum_f=rcfg.krum_f,
                                      multi_krum_m=rcfg.multi_krum_m)
                      if aggregator != "mean" else None)
        # Detection/quarantine layer: host-side state, fed by per-round
        # screened flags from the device step; checkpointed so resumed
        # runs replay it exactly.
        self._quarantine_on = bool(rcfg is not None
                                   and rcfg.quarantine_after > 0)
        self._quarantine_after = rcfg.quarantine_after if rcfg else 0
        self._quarantine_rounds = rcfg.quarantine_rounds if rcfg else 0
        self._screen_streak = np.zeros(w, np.int64)
        self._quarantine_until = np.zeros(w, np.int64)

        # Sharded weight-update hot path (ISSUE 5 tentpole): the masked
        # aggregation runs as reduce-scatter + 1/D-shard update + one
        # all-gather over size-bounded flat buckets instead of every
        # device redundantly forming the full replicated theta
        # (dopt.parallel.collectives.masked_average_scatter).  "off"
        # keeps the exact pre-change programs (python gating).
        if f.update_sharding not in ("off", "scatter"):
            raise ValueError(
                f"unknown update_sharding {f.update_sharding!r}; "
                "one of off|scatter")
        self._scatter = f.update_sharding == "scatter"
        if self._scatter:
            if aggregator != "mean":
                raise ValueError(
                    "update_sharding='scatter' shards the masked-MEAN "
                    f"reduce; aggregator={aggregator!r} is a full-"
                    "precision robust statistic over whole updates — "
                    "drop one of the two")
            if f.staleness_max > 0:
                raise ValueError(
                    "update_sharding='scatter' does not compose with "
                    "staleness-aware aggregation (its decay-weighted "
                    "sum runs on the unsharded tree) — drop one of "
                    "the two")
            if f.compact:
                raise ValueError(
                    "update_sharding='scatter' is a full-width sharded "
                    "reduce; FederatedConfig.compact gathers m lanes "
                    "and has no cross-worker collective to shard — "
                    "drop one of the two")
            if len(self.mesh.axis_names) != 1:
                raise ValueError(
                    "update_sharding='scatter' needs a flat 1-D worker "
                    f"mesh (got {self.mesh.shape}); hybrid (hosts × "
                    "ici) meshes keep the dense path")

        # Communication substrate schedule (ExperimentConfig.comm): the
        # federated aggregation speaks the same flat-bucket scatter
        # wire, so CommConfig.wire_dtype narrows the bucketed reduce
        # hop exactly like gossip's.  The qsgd bucket codec stays a
        # gossip-engine mode here: its error-feedback residual is
        # per-ROUND carried worker state, and the federated round
        # re-binds sampled clients onto lanes every round, so there is
        # no stable lane for the residual to live on.
        comm_cfg = cfg.comm
        if comm_cfg is not None:
            if not self._scatter:
                raise ValueError(
                    "the comm substrate schedule (ExperimentConfig.comm) "
                    "speaks the flat-bucket wire of "
                    "update_sharding='scatter'; set "
                    "federated.update_sharding='scatter' to arm it "
                    f"(got update_sharding={f.update_sharding!r})")
            if comm_cfg.codec != "none":
                raise ValueError(
                    f"comm.codec={comm_cfg.codec!r} needs a stable "
                    "per-lane error-feedback residual across rounds; "
                    "the federated round re-binds sampled clients onto "
                    "lanes, so run the codec on the gossip engine and "
                    "use comm.wire_dtype for federated wire narrowing")
            if f.comm_dtype and comm_cfg.wire_dtype:
                raise ValueError(
                    f"federated.comm_dtype={f.comm_dtype!r} and "
                    f"comm.wire_dtype={comm_cfg.wire_dtype!r} both name "
                    "a wire dtype; set exactly one (comm.wire_dtype is "
                    "the substrate-schedule spelling of the same knob)")

        # Staleness-aware aggregation (FederatedConfig.staleness_max):
        # instead of hard-dropping a deadline-missed straggler
        # (straggler_policy='drop') or a delay-faulted uplink
        # (FaultConfig.msg_delay), the client's finished update is
        # CAPTURED into a one-slot-per-worker device buffer and admitted
        # into the aggregate of round t+d with weight staleness_decay^d.
        # Admission passes the same non-finite screen as fresh updates
        # and respects quarantine, so it composes with the Byzantine
        # path.  Host bookkeeping (admit round / weight / origin) is
        # checkpointed, so killed-and-resumed runs replay admissions
        # bit-exactly.  Forces full-width per-round execution.
        if f.staleness_max < 0:
            raise ValueError("FederatedConfig.staleness_max must be >= 0")
        if not 0.0 < f.staleness_decay <= 1.0:
            raise ValueError(
                f"FederatedConfig.staleness_decay={f.staleness_decay} "
                "must be in (0, 1]")
        self._staleness_max = f.staleness_max
        self._staleness_decay = f.staleness_decay
        produces_late = (self.faults.active and cfg.faults is not None
                         and ((cfg.faults.straggle > 0
                               and cfg.faults.straggler_policy == "drop")
                              or cfg.faults.msg_delay > 0))
        self._has_stale = f.staleness_max > 0 and produces_late
        if f.staleness_max > 0:
            if f.algorithm not in ("fedavg", "fedprox"):
                raise ValueError(
                    "staleness-aware aggregation needs a stateless-"
                    "client algorithm (fedavg|fedprox): SCAFFOLD/ADMM "
                    "companion state has no late-admission semantics")
            if aggregator != "mean":
                raise ValueError(
                    "staleness-aware aggregation is a weighted mean; "
                    f"it does not compose with aggregator="
                    f"{aggregator!r} (selection/trimming have no "
                    "decayed-weight form here) — drop one of the two")
            if f.comm_dtype:
                raise ValueError(
                    "comm_dtype wire compression only applies to the "
                    "masked-mean reduce; the staleness-weighted "
                    "aggregate runs its own full-precision sum — drop "
                    "one of the two")
        self._stale_admit_round = np.zeros(w, np.int64)
        self._stale_weight = np.zeros(w, np.float64)
        self._stale_origin = np.zeros(w, np.int64)

        # Client population registry (ISSUE 6 tentpole, dopt.population):
        # decouple the client POPULATION (1k–10k host-side records) from
        # the device lanes.  Each round a stateless seeded sampler draws
        # a cohort, the cohort binds onto ceil(cohort/lanes) fixed-width
        # validity-masked lane WAVES, per-device partial weighted sums
        # accumulate across the waves inside one jitted scan, and ONE
        # cross-device bucketed reduce (masked_average_scatter with the
        # cohort-weight denominator) forms the aggregate.  Clients are
        # STATELESS FedAvg/FedProx participants — only their registry
        # row (shard assignment, participation, streaks, quarantine)
        # persists, keyed by CLIENT id so adversaries and sentences
        # survive re-sampling.  population=None keeps the exact
        # pre-population programs (python gating).
        self._registry = None
        pop = cfg.population
        if pop is not None:
            from dopt.population import (ClientRegistry,
                                         validate_population_config)

            validate_population_config(pop)
            if f.algorithm not in ("fedavg", "fedprox"):
                raise ValueError(
                    "population mode needs a stateless-client algorithm "
                    f"(fedavg|fedprox): {f.algorithm!r} carries "
                    "per-client companion state no registry row can hold")
            if cfg.data.local_holdout > 0:
                raise ValueError(
                    "population mode is incompatible with the local "
                    "train/val holdout (per-epoch client history needs "
                    "persistent per-client state) — drop one of the two")
            if f.compact:
                raise ValueError(
                    "FederatedConfig.compact=True is incompatible with "
                    "population mode (the wave loop IS the compact "
                    "execution: fixed-width lanes, validity as data)")
            if f.staleness_max > 0:
                raise ValueError(
                    "population mode does not compose with staleness-"
                    "aware aggregation (the one-slot-per-WORKER buffer "
                    "has no per-client form) — drop one of the two")
            if f.comm_dtype:
                raise ValueError(
                    "population mode's hierarchical reduce is its own "
                    "wire path; comm_dtype applies to the plain masked-"
                    "mean reduce only — drop one of the two")
            if self._scatter:
                raise ValueError(
                    "population mode always aggregates via the bucketed "
                    "scatter flat-tree path; keep update_sharding='off' "
                    "(the knob only retargets the lane engines)")
            if aggregator != "mean":
                raise ValueError(
                    "population mode streams per-wave partial SUMS; "
                    f"aggregator={aggregator!r} needs every update "
                    "materialised at once — drop one of the two")
            if cfg.mesh_hosts:
                raise ValueError(
                    "population mode runs its reduce over a flat 1-D "
                    "worker mesh; hybrid (hosts × ici) meshes are not "
                    "supported")
            if has_corrupt and cfg.faults.corrupt_mode == "stale":
                raise ValueError(
                    "corrupt_mode='stale' replays the worker's previous "
                    "update; population clients are stateless (no "
                    "previous update exists) — use nan|inf|scale|"
                    "signflip")
            lanes = int(pop.lanes or w)
            if lanes != w:
                # The wave width is an execution choice independent of
                # the shard count: rebuild the mesh around it (the
                # [W, ...] data-shard stacks still ride this mesh, so
                # the shard count must stay divisible).
                self.mesh = make_worker_mesh(lanes, cfg.mesh_devices,
                                             cfg.mesh_hosts)
                self._sharding = worker_sharding(self.mesh)
            if len(self.mesh.axis_names) != 1:
                raise ValueError(
                    "population mode needs a flat 1-D worker mesh "
                    f"(got {self.mesh.shape})")
            if lanes % self.mesh.size or w % self.mesh.size:
                raise ValueError(
                    f"population lanes={lanes} and data.num_users={w} "
                    f"must both divide the {self.mesh.size}-device mesh")
            self._registry = ClientRegistry(
                pop, num_shards=w, seed=cfg.seed, faults=cfg.faults,
                robust=rcfg, lanes=lanes)
            # Quarantine is CLIENT-keyed in population mode (the
            # registry's streaks); the lane-keyed machinery stays dark.
            self._quarantine_on = False

        # Prefetched host pipeline (dopt.data.prefetch): "on" makes the
        # blocked/chaos-blocked/population loops stage round/block b+1's
        # batch plans + participation inputs while b runs on device.
        # "off" (default) is the exact pre-change host loop.
        if f.prefetch not in ("off", "on"):
            raise ValueError(
                f"unknown prefetch {f.prefetch!r}; one of off|on")
        self._prefetch = f.prefetch == "on"
        # Per-round convergence diagnostics (FederatedConfig.
        # diagnostics): "on" computes the diag scalar block INSIDE the
        # compiled round (full-width, compact and fused-chaos paths —
        # it rides the packed host-metrics vector, so the blocked scans
        # carry it as one more stacked output) and emits it as
        # deterministic gauges at the post-fetch boundary, plus the
        # non-deterministic resource/compile channel when telemetry is
        # attached.  "off" (default) compiles the exact pre-change
        # programs — every use below is python-gated on it.
        if f.diagnostics not in ("off", "on"):
            raise ValueError(
                f"unknown diagnostics {f.diagnostics!r}; one of off|on")
        self._diag = f.diagnostics == "on"
        from dopt.obs.events import DIAG_GAUGES

        # The packed block's emission names: the shared five + this
        # engine's dispersion meter (round_diag's stack order).
        self._diag_keys = DIAG_GAUGES + ("lane_dispersion",)
        if self._diag and self._registry is not None:
            raise ValueError(
                "diagnostics='on' does not compose with population mode "
                "(wave clients are stateless — there is no lane-carried "
                "momentum/params for the convergence diagnostics to "
                "measure) — drop one of the two")
        from dopt.utils.profiling import CompileWatcher

        self._compile_watch = CompileWatcher()
        self._last_step_total = 0.0
        if (self._prefetch and self._registry is not None
                and rcfg is not None and rcfg.quarantine_after > 0):
            raise ValueError(
                "prefetch='on' does not compose with population-mode "
                "client quarantine: round t+1's cohort eligibility "
                "depends on round t's screen feedback, which only "
                "exists after the fetch — drop one of the two")

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
        # Local train/val holdout (reference train_val_test, P1
        # clients.py:16-34): training and the avg_trainig_calculator
        # train-eval run on the 90% sub-shard; every local epoch
        # evaluates the client's own val split (the first 10%).
        self._holdout, self._train_matrix, self._val = prepare_holdout(
            cfg, self.index_matrix, self.mesh, batch_size=f.local_bs)
        # Resident train features stay FLAT on device (see
        # flat_input_apply: shaped-row gathers are ~2.6× slower and
        # poison downstream layouts on TPU).
        self._sample_shape = self.dataset.train_x.shape[1:]
        ntr = self.dataset.train_x.shape[0]
        self._train_x = jnp.asarray(self.dataset.train_x.reshape(ntr, -1))
        self._train_y = jnp.asarray(self.dataset.train_y)
        ex, ey, ew = eval_batches(self.dataset.test_x, self.dataset.test_y,
                                  batch_size=max(f.local_bs, 256))
        self._eval = (jnp.asarray(ex), jnp.asarray(ey), jnp.asarray(ew))
        # Static per-worker train-eval stacks (sequential order) for the
        # avg_trainig_calculator metric (inference("train") — the TRAIN
        # sub-shard when the holdout is on).
        ti, tw = stacked_eval_batches(self._train_matrix,
                                      batch_size=max(f.local_bs, 256))
        self._train_eval_idx = jnp.asarray(ti)
        self._train_eval_w = jnp.asarray(tw)

        key = jax.random.key(cfg.seed)
        dummy = jnp.zeros((1, *cfg.model.input_shape))
        theta0 = self.model.init(key, dummy)["params"]
        # param_dtype: storage dtype of theta + the stacked worker state
        # (bf16 halves HBM + collective bytes; f32 is the parity mode).
        pdt = jnp.dtype(cfg.model.param_dtype)
        theta0 = jax.tree.map(lambda x: x.astype(pdt), theta0)
        self.param_count = count_params(theta0)
        # Global model: device-resident + replicated FROM CONSTRUCTION,
        # so the first jitted round sees the same input types as every
        # later one (a numpy theta would make call 2 retrace — the
        # trace cache keys on array type/sharding, and the round's
        # output theta is a committed device array).
        from dopt.parallel.mesh import replicated_sharding

        self._replicated = replicated_sharding(self.mesh)
        self.theta = jax.device_put(theta0, self._replicated)
        # Host-side broadcast from the single init — one |θ| fetch, not
        # a W·|θ| device→host round-trip (see gossip.py).
        t_host = jax.device_get(theta0)
        stacked = jax.tree.map(
            lambda x: np.broadcast_to(x[None], (w,) + x.shape), t_host)
        self.params = shard_worker_tree(stacked, self.mesh)
        self.momentum = shard_worker_tree(
            jax.tree.map(np.zeros_like, stacked), self.mesh)
        # Scatter-mode flat bucketing plan (static; compiled into the
        # round program).
        self._scatter_spec = (
            make_update_shard_spec(
                stacked, fold=self.mesh.size,
                bucket_bytes=int(f.update_bucket_mb * (1 << 20)))
            if self._scatter else None)
        # Population mode's bucketing plan: the cross-wave accumulator
        # is an f32 [lanes, ...] stacked tree (weighted sums accumulate
        # at full precision whatever param_dtype is), reduced once per
        # round through the same bucketed flat-tree path as
        # update_sharding='scatter'.
        self._pop_spec = (
            make_update_shard_spec(
                jax.tree.map(
                    lambda x: np.zeros(
                        (self._registry.lanes,) + x.shape, np.float32),
                    t_host),
                fold=self.mesh.size,
                bucket_bytes=int(f.update_bucket_mb * (1 << 20)))
            if self._registry is not None else None)
        # Staleness buffer: one pending (late) update slot per worker.
        self._stale_p = (
            shard_worker_tree(jax.tree.map(np.zeros_like, stacked),
                              self.mesh)
            if self._has_stale else None)
        # Worker-stacked companion state: ADMM duals (clients.py:120-123)
        # or SCAFFOLD client control variates c_i; both live sharded over
        # the worker axis.  SCAFFOLD additionally keeps the replicated
        # server control variate c.
        self.duals = (
            shard_worker_tree(jax.tree.map(np.zeros_like, stacked), self.mesh)
            if f.algorithm in ("fedadmm", "scaffold") else None
        )
        self.c_global = (
            jax.device_put(jax.tree.map(jnp.zeros_like, self.theta),
                           self._replicated)
            if f.algorithm == "scaffold" else None
        )

        # Fused mean+update epilogue (FederatedConfig.fused_update): the
        # masked average and the theta update land in ONE Pallas pass
        # over the flat buckets —  θ'_b = M(mask)·disp + θ_b  with
        # M(mask) the masked-mean contraction matrix
        # (dopt.parallel.collectives.mean_weight_matrix) and disp the
        # masked lane displacements p_t − θ.  Every output row is the
        # same new theta, so the carried ``self.theta`` HOLDS the
        # [W, ...] broadcast slab (rows bit-identical; row 0 is the
        # global model).  Equals masked_average to f32 reassociation —
        # the allclose, not bit-equal, contract.  "off" (default)
        # python-gates every use below and compiles the exact
        # pre-change programs.
        if f.fused_update not in ("off", "on"):
            raise ValueError(
                f"unknown fused_update {f.fused_update!r}; one of off|on")
        self._fused_on = f.fused_update == "on"
        if self._fused_on:
            if f.algorithm not in ("fedavg", "fedprox"):
                raise ValueError(
                    "fused_update='on' fuses the masked-mean contraction "
                    f"with the theta update; algorithm {f.algorithm!r} "
                    "carries companion state (SCAFFOLD controls / ADMM "
                    "duals) through the aggregate, which the fused "
                    "epilogue does not yet speak (fedavg|fedprox)")
            if aggregator != "mean":
                raise ValueError(
                    "fused_update='on' only applies to the masked-mean "
                    f"reduce; aggregator={aggregator!r} is a full-"
                    "precision robust contraction with no mixing-matrix "
                    "form — drop one of the two")
            if clip_radius > 0:
                raise ValueError(
                    "fused_update='on' does not compose with "
                    "RobustConfig.clip_radius (the ball projection "
                    "applies per lane BETWEEN the local step and the "
                    "mean, so the displacement contraction would skip "
                    "it) — drop one of the two")
            if has_corrupt:
                raise ValueError(
                    "fused_update='on' does not compose with corrupt "
                    "faults (the Byzantine injection rewrites lane "
                    "updates between the local step and the aggregate; "
                    "the robust defenses that make that meaningful are "
                    "unfused) — drop one of the two")
            if f.staleness_max > 0:
                raise ValueError(
                    "fused_update='on' does not compose with staleness-"
                    "aware aggregation (the admit-weighted sum over the "
                    "late buffer is not a masked mean) — drop one of "
                    "the two")
            if self._scatter:
                raise ValueError(
                    "update_sharding='scatter' already restructures the "
                    "aggregation hot path; fused_update='on' is the "
                    "single-device fusion of the same epilogue — drop "
                    "one of the two")
            if f.comm_dtype:
                raise ValueError(
                    "comm_dtype wire compression only applies to the "
                    "plain masked-average collective; the fused "
                    "epilogue contracts at f32 in one HBM pass — drop "
                    "one of the two")
            if f.compact:
                raise ValueError(
                    "FederatedConfig.compact=True is incompatible with "
                    "fused_update='on': the fused epilogue contracts "
                    "the full [W, ...] slab (compact's gathered-lane "
                    "mean has no fixed-width contraction) — drop one "
                    "of the two")
            if self._registry is not None:
                raise ValueError(
                    "fused_update='on' does not compose with population "
                    "mode (waves accumulate into an f32 lane "
                    "accumulator, not a masked mean over the carried "
                    "slab) — drop one of the two")
            if self.mesh.size > 1:
                raise ValueError(
                    "fused_update='on' needs a single-device worker "
                    f"mesh (got {self.mesh.shape}): the Pallas epilogue "
                    "contracts the full worker axis in one kernel call; "
                    "multi-device meshes keep the dense or scatter "
                    "paths")
            # theta becomes the worker-axis broadcast slab from
            # CONSTRUCTION, so the first jitted round sees the slab
            # type/sharding every later round produces.
            self.theta = shard_worker_tree(stacked, self.mesh)
        fused_on = self._fused_on
        self._fused_spec = (
            make_update_shard_spec(
                stacked, fold=self.mesh.size,
                bucket_bytes=int(f.update_bucket_mb * (1 << 20)))
            if self._fused_on else None)
        fused_spec = self._fused_spec
        if self._fused_on:
            from dopt.ops.fused_update import fused_mix_update
            from dopt.parallel.collectives import mean_weight_matrix
        else:
            fused_mix_update = mean_weight_matrix = None

        local_algorithm = {"fedavg": "sgd", "fedprox": "fedprox",
                           "fedadmm": "fedadmm", "scaffold": "scaffold"}[f.algorithm]
        # Grouped stacked-forward fast path (see gossip.py / zoo.py).
        from dopt.models.zoo import resolve_stacked_apply

        s_apply = resolve_stacked_apply(self.model, cfg.model.stacked_impl)
        app_f = flat_input_apply(self.model.apply, self._sample_shape)
        s_apply_f = (flat_input_stacked_apply(s_apply, self._sample_shape)
                     if s_apply is not None else None)
        local = make_stacked_local_update(
            app_f, lr=cfg.optim.lr, momentum=cfg.optim.momentum,
            algorithm=local_algorithm,
            rho=cfg.optim.rho, l2=cfg.optim.weight_decay,
            update_impl="pallas" if cfg.optim.fused_update else "jnp",
            stacked_apply=s_apply_f, clip_norm=cfg.optim.clip_norm,
            with_limit=may_straggle,
        )
        # Per-epoch big-gather chunking (see gossip.py: per-step gathers
        # carry ~250 µs fixed overhead each on a v5e; slab gathers don't).
        from dopt.engine.local import pick_gather_chunks

        l_shard = self._train_matrix.shape[1]
        bs_eff = min(f.local_bs, l_shard)
        spe = -(-l_shard // bs_eff)
        sample_bytes = (int(np.prod(self.dataset.train_x.shape[1:]))
                        * self.dataset.train_x.dtype.itemsize)
        epoch_chunks = pick_gather_chunks(
            spe, workers=w, batch=bs_eff, sample_bytes=sample_bytes)
        # Straggler-deadline granularity (dopt.faults): the holdout's
        # epoch loop gates per EPOCH, the flat path per SGD step.
        self._straggle_units = (f.local_ep if self._holdout
                                else f.local_ep * spe)
        local_epochs = (
            make_stacked_local_update_epochs(
                app_f, lr=cfg.optim.lr,
                momentum=cfg.optim.momentum, algorithm=local_algorithm,
                rho=cfg.optim.rho, l2=cfg.optim.weight_decay,
                update_impl="pallas" if cfg.optim.fused_update else "jnp",
                gather_chunks=epoch_chunks, stacked_apply=s_apply_f,
                clip_norm=cfg.optim.clip_norm, with_limit=may_straggle)
            if self._holdout else None
        )
        if s_apply_f is not None and self.mesh.size > 1:
            # Multi-device + grouped stacked forward: run the local phase
            # under shard_map (dopt.parallel.mesh.shard_over_workers) —
            # per-device lanes, local feature-group count, zero
            # collectives.  Only the full-width path exists on a
            # multi-device mesh (_use_compact), so every lane count here
            # is the mesh-divisible W.  theta/c_global ride replicated,
            # ADMM duals / SCAFFOLD client controls worker-sharded.
            from dopt.parallel.mesh import shard_over_workers

            extra = {"sgd": "", "fedprox": "r",
                     "fedadmm": "rw", "scaffold": "rw"}[local_algorithm]
            local = shard_over_workers(
                local, self.mesh,
                "w" * (6 if may_straggle else 5) + extra, "w" * 4)
            if local_epochs is not None:
                local_epochs = shard_over_workers(
                    local_epochs, self.mesh,
                    ("wwwwwrrww" if may_straggle else "wwwwrrww") + extra,
                    "www")
        use_holdout = self._holdout
        local_ep_n = f.local_ep
        global_eval = make_evaluator(self.model.apply)
        algorithm = f.algorithm
        # comm_dtype applies on ANY mesh size (a 1-device mesh still
        # quantizes, matching the gossip engine, so single-device debug
        # runs reproduce multi-device numerics).
        agg_mesh = self.mesh
        agg_comm = jnp.dtype(f.comm_dtype) if f.comm_dtype else None
        if cfg.comm is not None and cfg.comm.wire_dtype:
            agg_comm = jnp.dtype(cfg.comm.wire_dtype)
        scatter_spec = self._scatter_spec
        rho = cfg.optim.rho
        lr = cfg.optim.lr
        momentum_coef = cfg.optim.momentum
        eval_train_flag = eval_train

        def run_local(start, mom_in, idx, bw, limits, train_x, train_y,
                      vidx, vw, theta=None, alpha=None):
            """Dispatch the local-training phase on however many lanes
            the inputs carry: flat step scan over the shard (idiomatic)
            or, with the holdout on, the reference's epoch loop with
            per-epoch local-val eval.  Returns (p, m, losses, accs, em)
            with losses/accs per-step [lanes, S] or per-epoch [lanes, E]
            (``mean(axis=1)`` is the round metric either way) and em the
            per-epoch history arrays ({} when the holdout is off).
            ``limits`` is the per-lane straggler work budget
            (dopt.faults), consumed only when the plan can straggle."""
            if use_holdout:
                lanes = idx.shape[0]
                se = idx.shape[1] // local_ep_n
                idx_e = idx.reshape(lanes, local_ep_n, se, idx.shape[2])
                bw_e = bw.reshape(idx_e.shape)
                args = ((start, mom_in, idx_e, bw_e, limits, train_x,
                         train_y, vidx, vw) if may_straggle else
                        (start, mom_in, idx_e, bw_e, train_x, train_y,
                         vidx, vw))
                if algorithm == "fedavg":
                    p_t, m_t, em = local_epochs(*args)
                elif algorithm == "fedprox":
                    p_t, m_t, em = local_epochs(*args, theta)
                else:
                    p_t, m_t, em = local_epochs(*args, theta, alpha)
                return p_t, m_t, em["train_loss"], em["train_acc"], em
            bx, by = gather_rows(train_x, train_y, idx)
            args = ((start, mom_in, bx, by, bw, limits) if may_straggle
                    else (start, mom_in, bx, by, bw))
            if algorithm == "fedavg":
                p_t, m_t, losses, accs = local(*args)
            elif algorithm == "fedprox":
                p_t, m_t, losses, accs = local(*args, theta)
            else:
                p_t, m_t, losses, accs = local(*args, theta, alpha)
            return p_t, m_t, losses, accs, {}

        @jax.named_scope("dopt_local")
        def algo_step(theta, start, mom_in, duals_in, c_global, idx, bw,
                      limits, train_x, train_y, vidx, vw):
            """Local update + companion-state refresh on however many
            lanes the inputs carry (all N for the full-width path, the m
            sampled for the compact path).  Returns (p_t, m_t, losses,
            accs, sub_new, em) where sub_new is the updated companion
            state for THESE lanes (ADMM duals after ascent / SCAFFOLD
            controls after the option-II refresh; unchanged for
            fedavg/fedprox).  The caller masks or scatters sub_new back
            into the worker-stacked state and forms the server-control
            update."""
            if algorithm == "fedavg":
                p_t, m_t, losses, accs, em = run_local(
                    start, mom_in, idx, bw, limits, train_x, train_y,
                    vidx, vw)
                sub_new = duals_in
            elif algorithm == "fedprox":
                p_t, m_t, losses, accs, em = run_local(
                    start, mom_in, idx, bw, limits, train_x, train_y,
                    vidx, vw, theta=theta)
                sub_new = duals_in
            elif algorithm == "scaffold":
                # Sampled workers restart from theta with a FRESH momentum
                # buffer so theta − y_i reflects only this round's
                # gradients (no stale-round momentum in the control
                # refresh); effective step size lr/(1−μ) accounts for
                # heavy-ball amplification of the displacement.
                mom0 = jax.tree.map(jnp.zeros_like, mom_in)
                p_t, m_t, losses, accs, em = run_local(
                    start, mom0, idx, bw, limits, train_x, train_y,
                    vidx, vw, theta=c_global, alpha=duals_in)
                steps = bw.shape[1]
                lr_eff = lr / max(1.0 - momentum_coef, 1e-8)
                if may_straggle:
                    # Each lane refreshes its control with ITS executed
                    # step count (a straggler's displacement theta − y_i
                    # reflects only the steps it finished): limits are
                    # epochs under the holdout, SGD steps otherwise.
                    steps_exec = (limits * (steps // local_ep_n)
                                  if use_holdout
                                  else jnp.minimum(limits, steps))
                    sub_new = jax.vmap(
                        lambda ci, y, ns: scaffold_control_update(
                            ci, c_global, theta, y, lr=lr_eff,
                            num_steps=ns),
                        in_axes=(0, 0, 0),
                    )(duals_in, p_t, steps_exec)
                else:
                    sub_new = jax.vmap(
                        lambda ci, y: scaffold_control_update(
                            ci, c_global, theta, y, lr=lr_eff,
                            num_steps=steps),
                        in_axes=(0, 0),
                    )(duals_in, p_t)
            else:
                p_t, m_t, losses, accs, em = run_local(
                    start, mom_in, idx, bw, limits, train_x, train_y,
                    vidx, vw, theta=theta, alpha=duals_in)
                sub_new = jax.vmap(
                    lambda a, p: admm_dual_ascent(a, p, theta, rho),
                    in_axes=(0, 0),
                )(duals_in, p_t)
            return p_t, m_t, losses, accs, sub_new, em

        def control_delta(c_global, sub_new, sub_old):
            """SCAFFOLD server control: c ← c + (1/N)·Σ_{i∈S}(c_i⁺ − c_i);
            the caller passes lane sets where non-sampled deltas are 0
            (full-width, post-mask) or absent (compact)."""
            return jax.tree.map(
                lambda c, dn, do: c + (dn - do).sum(axis=0) / w,
                c_global, sub_new, sub_old,
            )

        has_stale = self._has_stale
        st_clip = clip_radius
        diag_on = self._diag
        _g_norm = global_norm_f32

        def round_diag(p_lanes, p_start, m_new, theta_new, p_fleet,
                       losses, mask):
            """[6] f32 per-round diagnostics (dopt.obs.events.DIAG_GAUGES
            + lane_dispersion), computed ON DEVICE from the round's
            carried state so every execution path agrees bit-for-bit:
            global L2 of the AGGREGATING lanes' displacement from their
            round-start load (``p_lanes`` − ``p_start`` masked by
            ``mask`` — a screened lane's carry reverts to its stale
            pre-round params while its start was the theta load, so an
            unmasked sum would read that accumulated drift as a giant
            round update and false-fire grad_explosion; compact padding
            lanes are masked out the same way), of the carried momentum
            (zero for scaffold's per-round-local buffer), and of the
            NEW global model; the aggregating-lane train-loss mean and
            max−min spread; and the fleet dispersion
            mean_i ||p_i − theta|| over ALL W carried lanes (stale-lane
            drift is the signal)."""
            upd = jnp.sqrt((lane_sq_norms(jax.tree.map(
                lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
                p_lanes, p_start)) * mask).sum())
            lane = losses.mean(axis=1).astype(jnp.float32)
            # The always-on screen keeps the carried trees finite, but a
            # lane can pass it (finite params) while its train loss
            # overflowed — fold loss-finiteness into the mask so one such
            # lane can't blind the fleet loss meters (mirrors gossip's
            # diagnosable-lane mask).
            okl = mask * jnp.isfinite(lane)
            denom = jnp.maximum(okl.sum(), 1.0)
            lmean = (jnp.where(okl > 0, lane, 0.0)).sum() / denom
            lmax = jnp.where(okl > 0, lane, -jnp.inf).max()
            lmin = jnp.where(okl > 0, lane, jnp.inf).min()
            spread = jnp.where(okl.sum() > 0, lmax - lmin, 0.0)
            sq = None
            for x, th in zip(jax.tree.leaves(p_fleet),
                             jax.tree.leaves(theta_new)):
                d = (x.astype(jnp.float32)
                     - th.astype(jnp.float32)[None]).reshape(x.shape[0], -1)
                s = (d * d).sum(axis=1)
                sq = s if sq is None else sq + s
            disp = jnp.sqrt(sq).mean()
            return jnp.stack([upd, _g_norm(m_new), _g_norm(theta_new),
                              lmean, spread, disp])

        def pack_host_metrics(local_loss, evalm, trainm, em, screened,
                              stale_scr=None, diag=None):
            """Everything the host reads per round, as ONE flat f32
            vector — every device→host fetch synchronises with the
            device, so the round's history metrics
            (local loss, global eval, worker-mean train eval, the
            non-finite-screen flags, and the per-epoch client-history
            block under the holdout) travel in a single transfer.
            Layout (mirrored by ``_unpack_host_metrics``): [local_loss,
            test_acc, test_loss_sum, mean(train_loss), mean(train_acc)]
            + [lanes] screened flags + (staleness runs only) [lanes]
            screened-on-admission flags + 4×[lanes·E] em blocks."""
            parts = [local_loss.reshape(1),
                     evalm["acc"][None], evalm["loss_sum"][None],
                     jnp.mean(trainm["loss_mean"])[None],
                     jnp.mean(trainm["acc"])[None],
                     screened.ravel()]
            if has_stale:
                parts.append(stale_scr.ravel())
            if use_holdout:
                parts += [em["train_loss"].ravel(), em["train_acc"].ravel(),
                          em["val_acc"].ravel(), em["val_loss_sum"].ravel()]
            if diag_on:
                # Diagnostics block travels LAST so every earlier
                # offset (_unpack_host_metrics, the chaos scan's
                # screened-flag slice) is layout-stable.
                parts.append(diag)
            return jnp.concatenate([p.astype(jnp.float32) for p in parts])

        def finish(new_theta, new_p, new_m, new_duals, new_c, local_loss,
                   em, screened, train_x, train_y, ex, ey, ew, tidx,
                   tweight, stale_scr=None, diag=None):
            """Shared round tail: global test eval + all-client train eval
            (``avg_trainig_calculator``) — identical for both execution
            paths so the history schema can never diverge between them.
            The host-facing metrics leave as one packed vector."""
            evalm = global_eval(new_theta, ex, ey, ew)
            if eval_train_flag:
                with jax.named_scope("dopt_eval"):
                    tx, ty = gather_rows(train_x, train_y, tidx)
                trainm = stacked_eval_perworker(new_p, tx, ty, tweight)
            else:
                trainm = {"acc": jnp.zeros(w), "loss_mean": jnp.zeros(w),
                          "loss_sum": jnp.zeros(w), "count": jnp.ones(w)}
            return (new_theta, new_p, new_m, new_duals, new_c,
                    pack_host_metrics(jnp.asarray(local_loss), evalm,
                                      trainm, em, screened, stale_scr,
                                      diag))

        def round_fn(theta, params, mom, duals, c_global, mask, limits, idx,
                     bweight, train_x, train_y, ex, ey, ew, tidx, tweight,
                     vidx, vw, cmask=None, load_mask=None, stale_p=None,
                     admit_w=None, capture=None):
            if fused_on:
                # ``theta`` carries the [W, ...] broadcast slab (rows
                # bit-identical); consumers of the single global model
                # read row 0.
                theta_b, theta = theta, jax.tree.map(lambda x: x[0],
                                                     theta)
            else:
                theta_b = broadcast_to_workers(theta, w)
            # Staleness runs load theta into every lane that TRAINS this
            # round (the sampled aggregators AND the captured late
            # senders); only `mask` lanes enter the immediate aggregate.
            start = _where_mask(load_mask if has_stale else mask,
                                theta_b, params)
            p_t, m_t, losses, accs, sub_new, em = algo_step(
                theta, start, mom, duals, c_global, idx, bweight, limits,
                train_x, train_y, vidx, vw)
            if has_corrupt:
                # Byzantine injection INSIDE the jitted round (the lanes
                # flagged by the plan's stateless per-round draw lie
                # about their update), so corrupted runs stay
                # bit-reproducible and block/compact/resume-exact.
                p_t = corrupt_update(p_t, cmask, corrupt_mode,
                                     corrupt_scale, ref=theta, prev=params)
                if algorithm in ("scaffold", "fedadmm"):
                    # A liar lies on EVERY channel it reports: its
                    # companion-state update (SCAFFOLD control / ADMM
                    # dual) is corrupted under the same mask.  Note the
                    # robust aggregators defend theta only — the
                    # companion channel reaches c_global/duals
                    # unaggregated, a real SCAFFOLD-under-Byzantine
                    # exposure (see docs/ARCHITECTURE.md Threat model).
                    sub_new = corrupt_update(sub_new, cmask, corrupt_mode,
                                             corrupt_scale, prev=duals)
            # Non-finite screen — always on, the guard on the default
            # mean path: a lane whose update carries NaN/Inf is treated
            # as failed for the round, excluded from the aggregate AND
            # from the carried state so the poison never propagates.
            fin = finite_lane_mask(p_t)
            agg_mask = mask * fin
            if algorithm in ("scaffold", "fedadmm"):
                new_duals = _where_mask(agg_mask, sub_new, duals)
            else:
                new_duals = duals
            new_c = (control_delta(c_global, new_duals, duals)
                     if algorithm == "scaffold" else c_global)
            new_p = _where_mask(agg_mask, p_t, params)
            # Scaffold momentum is per-round-local (fresh buffer each
            # round), so the carried buffer stays untouched zeros and is
            # not checkpointed; the other algorithms persist it like the
            # reference's lifetime client optimizers.
            new_m = (mom if algorithm == "scaffold"
                     else _where_mask(agg_mask, m_t, mom))
            agg_in = (clip_to_ball(new_p, theta, clip_radius)
                      if clip_radius > 0 else new_p)
            if has_stale:
                # Staleness-weighted aggregation: the round's fresh
                # survivors at weight 1 plus the admitted late updates
                # at their decay weights, one normalised weighted sum.
                # Admitted updates pass the non-finite screen (a lane
                # that went NaN while buffered enters at weight 0) and
                # the same clip-to-ball as fresh ones.
                fin_s = finite_lane_mask(stale_p)
                aw = admit_w * fin_s
                # Zero the non-finite buffer lanes BEFORE the weighted
                # sum: a 0-weighted NaN still poisons the contraction
                # (0·NaN = NaN) — same guard the gossip robust path
                # applies to non-finite sends.
                stale_z = _where_mask(fin_s, stale_p,
                                      jax.tree.map(jnp.zeros_like, stale_p))
                agg_stale = (clip_to_ball(stale_z, theta, st_clip)
                             if st_clip > 0 else stale_z)
                tot_w = agg_mask.sum() + aw.sum()
                # Guard only the zero-weight round (theta passes through
                # via alive_any below): clamping to 1.0 would SHRINK
                # theta on a round whose total admitted weight is < 1
                # (e.g. a lone decay-weighted admission).
                denom = jnp.where(tot_w > 0, tot_w, 1.0)

                def wleaf(x, s):
                    mm = agg_mask.reshape(
                        (-1,) + (1,) * (x.ndim - 1)).astype(x.dtype)
                    ss = aw.reshape(
                        (-1,) + (1,) * (x.ndim - 1)).astype(x.dtype)
                    return (((x * mm).sum(axis=0) + (s * ss).sum(axis=0))
                            / denom.astype(x.dtype))

                with jax.named_scope("dopt_mix"):
                    new_theta = jax.tree.map(wleaf, agg_in, agg_stale)
                alive_any = tot_w > 0
                # Captured lanes' finished updates land in the buffer;
                # everyone else's slot is carried unchanged.
                new_stale = _where_mask(capture, p_t, stale_p)
                stale_scr = (admit_w > 0).astype(jnp.float32) * (1.0 - fin_s)
            else:
                if fused_on:
                    # ONE HBM pass over the flat buckets: masked-mean
                    # contraction + theta update fuse —
                    # θ'_b = M(agg_mask)·disp + θ_b, every row the new
                    # theta.  disp is masked (not just weighted) to
                    # zero: a screened lane's NaN would poison the
                    # contraction through 0·NaN otherwise.  An all-dead
                    # round has M = 0, so θ_b passes through exactly —
                    # no extra where needed.
                    disp = _where_mask(
                        agg_mask,
                        jax.tree.map(lambda a, b: a - b, p_t, theta_b),
                        jax.tree.map(jnp.zeros_like, p_t))
                    with jax.named_scope("dopt_mix"):
                        theta_slab = fused_mix_update(
                            disp, theta_b, mean_weight_matrix(agg_mask),
                            fused_spec, lr=-1.0)
                    new_theta = jax.tree.map(lambda x: x[0], theta_slab)
                elif agg_robust is not None:
                    with jax.named_scope("dopt_mix"):
                        new_theta = agg_robust(agg_in, agg_mask)
                elif scatter_spec is not None:
                    new_theta = masked_average_scatter(
                        agg_in, agg_mask, agg_mesh, scatter_spec,
                        comm_dtype=agg_comm)
                else:
                    new_theta = masked_average(agg_in, agg_mask,
                                               mesh=agg_mesh,
                                               comm_dtype=agg_comm)
                alive_any = agg_mask.sum() > 0
                new_stale, stale_scr = None, None
            # A round with zero surviving (unscreened) updates leaves
            # the global model unchanged (the aggregate over zero
            # survivors would otherwise zero theta).  The fused slab
            # already passes theta through (M = 0) and must not meet
            # the single-tree where.
            if not fused_on:
                new_theta = jax.tree.map(
                    lambda a, th: jnp.where(alive_any, a, th), new_theta,
                    theta)
            lane_loss = losses.mean(axis=1)
            lane_loss = jnp.where(jnp.isfinite(lane_loss), lane_loss, 0.0)
            local_loss = ((lane_loss * agg_mask).sum()
                          / jnp.maximum(agg_mask.sum(), 1))
            # Sampled-and-screened flags travel to the host for the
            # ledger and the quarantine streaks.
            screened = mask * (1.0 - fin)
            # Diagnostics from the CARRIED state: displacement of the
            # carried lanes from their round-start load, carried
            # momentum, the new global model, and the full-width fleet
            # dispersion.
            diag = (round_diag(new_p, start, new_m, new_theta, new_p,
                               losses, agg_mask)
                    if diag_on else None)
            # Full-width packs ALL W lanes' em rows (gathering the
            # sampled subset would be a dynamic shape); the host slices
            # by the round's sample before appending client rows.
            out = finish(new_theta, new_p, new_m, new_duals, new_c,
                         local_loss, em, screened, train_x, train_y, ex,
                         ey, ew, tidx, tweight, stale_scr, diag)
            if fused_on:
                # Carry position 0 is the slab; eval/diag above consumed
                # its row 0.
                return (theta_slab, *out[1:])
            if has_stale:
                return (*out[:5], new_stale, out[5])
            return out

        # Per-worker train-split eval: every input has a worker axis.
        # Batches come from the FLAT resident train arrays (finish()
        # gathers tx = train_x[tidx]), so both variants use the
        # flat-row apply adapters.  Params arrive in STANDARD layout
        # (the round's new_p), so the eval uses the standard stacked
        # apply even when the training loop runs the fast-layout codec.
        if s_apply is not None:
            s_eval_f = flat_input_stacked_apply(s_apply, self._sample_shape)

            def stacked_eval_perworker(p, ex_, ey_, ew_):
                return _stacked_eval_scan(s_eval_f, p, ex_.swapaxes(0, 1),
                                          ey_.swapaxes(0, 1),
                                          ew_.swapaxes(0, 1))
            if self.mesh.size > 1:
                from dopt.parallel.mesh import shard_over_workers

                stacked_eval_perworker = shard_over_workers(
                    stacked_eval_perworker, self.mesh, "wwww", "w")
        else:
            stacked_eval_perworker = jax.vmap(
                lambda p, ex_, ey_, ew_: make_evaluator(app_f)(p, ex_, ey_, ew_),
                in_axes=(0, 0, 0, 0),
            )

        def _take(tree, sel):
            return jax.tree.map(lambda x: x[sel], tree)

        def _scatter(tree, sel, sub):
            return jax.tree.map(lambda x, s: x.at[sel].set(s), tree, sub)

        def compact_round_fn(theta, params, mom, duals, c_global, sel,
                             limits_sel, idx_sel, bw_sel, train_x, train_y,
                             ex, ey, ew, tidx, tweight, vidx, vw,
                             cmask=None, valid=None):
            """Compact-sampling fast path: only the m = len(sel) sampled
            workers' lanes are trained ([m, ...] gather → local update →
            scatter-back), instead of all N lanes computing and the mask
            discarding N−m results.  Identical math to ``round_fn`` up to
            float summation order (the sampled average sums m terms
            directly rather than N mask-weighted ones).  Under fault
            injection ``sel`` carries the round's SURVIVORS (the host
            drops crashed / unreachable / deadline-dropped clients before
            the device step), so the sampled mean is the masked average
            over survivors, same as the full-width path.  Survivor
            counts vary round to round and jit retraces per distinct
            count — acceptable on the single-device (CPU-compile)
            meshes this path is restricted to; heavily-faulted sharded
            runs use the full-width path, whose shapes never change."""
            m = sel.shape[0]
            start = broadcast_to_workers(theta, m)
            duals_sel = _take(duals, sel)
            prev_sel = _take(params, sel)
            p_t, m_t, losses, accs, sub_new, em = algo_step(
                theta, start, _take(mom, sel), duals_sel, c_global,
                idx_sel, bw_sel, limits_sel, train_x, train_y,
                vidx[sel], vw[sel])
            if has_corrupt:
                p_t = corrupt_update(p_t, cmask, corrupt_mode,
                                     corrupt_scale, ref=theta, prev=prev_sel)
                if algorithm in ("scaffold", "fedadmm"):
                    # Same companion-channel lie as the full-width path.
                    sub_new = corrupt_update(sub_new, cmask, corrupt_mode,
                                             corrupt_scale, prev=duals_sel)
            # Non-finite screen over the m survivor lanes — a screened
            # lane keeps its stale state and leaves the aggregate, same
            # semantics as the full-width path.  ``all_fin`` selects the
            # exact pre-robust expressions when nothing was screened, so
            # clean compact runs stay bit-identical.
            fin = finite_lane_mask(p_t)
            if valid is not None:
                # Fixed-width fault lanes (the sorted-position-weighting
                # idea from dopt.robust applied to sampling): the m lane
                # slots are always filled — survivors first, then
                # padding ids whose results are discarded — and the
                # round's survivor count is DATA in ``valid``, not a
                # shape.  One compiled program serves every faulted
                # round, which is what makes compact+faults fuse into
                # blocks (and stop retracing per survivor count).
                # Folding validity into ``fin`` gives padding lanes the
                # screened-lane treatment everywhere below: excluded
                # from the aggregate, scatter-back is a self-write.
                fin = fin * valid
            all_fin = fin.min() >= 1.0
            sub_new_g = _where_mask(fin, sub_new, duals_sel)
            if algorithm in ("scaffold", "fedadmm"):
                new_duals = _scatter(duals, sel, sub_new_g)
            else:
                new_duals = duals
            new_c = (control_delta(c_global, sub_new_g, duals_sel)
                     if algorithm == "scaffold" else c_global)
            p_keep = _where_mask(fin, p_t, prev_sel)
            new_p = _scatter(params, sel, p_keep)
            new_m = (mom if algorithm == "scaffold"
                     else _scatter(mom, sel,
                                   _where_mask(fin, m_t, _take(mom, sel))))
            agg_in = (clip_to_ball(p_keep, theta, clip_radius)
                      if clip_radius > 0 else p_keep)
            # The aggregation layer of this path: a local mean over the
            # m sampled lanes (no ``masked_average``, so the scope is here).
            with jax.named_scope("dopt_mix"):
                if agg_robust is None:
                    plain = jax.tree.map(lambda x: x.mean(axis=0), agg_in)
                    masked = masked_mean(agg_in, fin)
                    new_theta = jax.tree.map(
                        lambda a, b: jnp.where(all_fin, a, b), plain, masked)
                else:
                    new_theta = agg_robust(agg_in, fin)
            any_fin = fin.sum() > 0
            new_theta = jax.tree.map(
                lambda a, th: jnp.where(any_fin, a, th), new_theta, theta)
            lane_loss = losses.mean(axis=1)
            lane_loss = jnp.where(jnp.isfinite(lane_loss), lane_loss, 0.0)
            local_loss = jnp.where(
                all_fin, losses.mean(),
                (lane_loss * fin).sum() / jnp.maximum(fin.sum(), 1))
            # Compact diagnostics: the m trained lanes' carried
            # displacement from theta, the fleet dispersion over the
            # scattered-back full-width state.  Same definitions as the
            # full-width path up to the lane set (compact-vs-full-width
            # numerics already differ by summation order).
            diag = (round_diag(p_keep, start, new_m, new_theta, new_p,
                               losses, fin)
                    if diag_on else None)
            return finish(new_theta, new_p, new_m, new_duals, new_c,
                          local_loss, em, 1.0 - fin, train_x, train_y, ex,
                          ey, ew, tidx, tweight, diag=diag)

        # Fused runs additionally donate the theta slab (arg 0): the
        # kernel aliases θ_b's pages into the new slab, so the
        # restructured carry costs zero extra HBM.  Off-path jit params
        # — and therefore the fingerprinted programs — are unchanged.
        _theta_donate = (0, 1, 2, 3) if fused_on else (1, 2, 3)
        self._round_fn = jax.jit(round_fn, donate_argnums=_theta_donate)
        self._compact_fn = jax.jit(compact_round_fn, donate_argnums=(1, 2, 3))

        def make_block_fn(one_round, with_valid=False):
            """k rounds fused into one lax.scan dispatch (jit retraces
            per distinct k).  Each iteration is one full reference round
            — sampled-client theta load, local epochs, masked average,
            global + per-client train eval — so history rows are
            identical to the per-round path's.  Under corrupt faults the
            per-round corrupt masks ride the scan as one more stacked
            input; ``with_valid`` additionally threads the fixed-width
            compact path's per-round validity masks.  The clean
            signature (and compiled program) is unchanged."""

            def block_fn(theta, params, mom, duals, c_global, gates,
                         limits, idxs, bws, train_x, train_y, ex, ey, ew,
                         tidx, tweight, vidx, vw, cmasks=None,
                         valids=None):
                def body(carry, xs):
                    th, p, m, d, c = carry
                    xs = list(xs)
                    gate, lim = xs[0], xs[1]
                    i = 2
                    kw = {}
                    if has_corrupt:
                        kw["cmask"] = xs[i]
                        i += 1
                    if with_valid:
                        kw["valid"] = xs[i]
                        i += 1
                    idx, bw = xs[i], xs[i + 1]
                    th, p, m, d, c, packed = one_round(
                        th, p, m, d, c, gate, lim, idx, bw,
                        train_x, train_y, ex, ey, ew, tidx, tweight,
                        vidx, vw, **kw)
                    return (th, p, m, d, c), packed

                xs = [gates, limits]
                if has_corrupt:
                    xs.append(cmasks)
                if with_valid:
                    xs.append(valids)
                xs += [idxs, bws]
                carry, packed = jax.lax.scan(
                    body, (theta, params, mom, duals, c_global),
                    tuple(xs))
                return (*carry, packed)

            return jax.jit(block_fn, donate_argnums=_theta_donate)

        self._block_fn = make_block_fn(round_fn)
        self._compact_block_fn = make_block_fn(compact_round_fn)
        self._compact_fault_block_fn = make_block_fn(compact_round_fn,
                                                     with_valid=True)

        # ---- fused chaos block (quarantine and/or staleness) ----------
        # The modes that used to force per-round execution did so
        # because their round-to-round state lived on the HOST: the
        # quarantine streaks fed next round's participation, and the
        # staleness buffer's capture/admit schedule was host
        # bookkeeping.  Here that state is scan CARRY (int32/f32
        # vectors + the one-slot [W, ...] buffer) and the round's
        # PARTICIPATION itself is computed on device from the
        # pre-drawn candidate list: the elif-chain of
        # ``_round_participation`` becomes branch masks, the
        # keep-first-m survivor cut a cumsum over draw order, and
        # admission weights a ``decay_pow`` table gather — all data,
        # no shapes.  The host replays the identical integer logic
        # post-fetch for the ledger (same rows, same order).
        q_on, q_after = self._quarantine_on, self._quarantine_after
        q_rounds = self._quarantine_rounds
        drop_policy_s = (cfg.faults is not None
                         and cfg.faults.straggler_policy == "drop")
        s_max = self._staleness_max
        # f32(f64 decay**d) per d — the exact value the host admission
        # path produces via np.float32(self._stale_weight[i]).
        self._decay_pow = np.asarray(
            [np.float32(float(f.staleness_decay) ** d)
             for d in range(max(s_max, 1) + 1)], np.float32)
        decay_pow = jnp.asarray(self._decay_pow)

        def device_participation(t, chosen, quar, away, crashed, unreach,
                                 straggler, up_drop, up_delay, late_d,
                                 m_cut):
            """Round t's participation decisions as device math, in the
            exact priority order of the host elif-chain (quarantine >
            churn > crash > partition > straggler-deadline > uplink
            drop > uplink delay > survivor).  Returns (mask, cap,
            d_vec): the [W] aggregating-survivor mask, the [W] capture
            mask (has_stale), and the capture lateness per worker."""
            q_c = quar[chosen]
            excl = (q_c | (away[chosen] > 0) | (crashed[chosen] > 0)
                    | (unreach[chosen] > 0))
            sg_c = (straggler[chosen] > 0) & ~excl
            strag_branch = sg_c if drop_policy_s else jnp.zeros_like(q_c)
            after_strag = excl | strag_branch
            ud_c = (up_drop[chosen] > 0) & ~after_strag
            dl = up_delay[chosen]
            dl_c = (dl > 0) & ~after_strag & ~(up_drop[chosen] > 0)
            survivor_ok = ~(after_strag | ud_c | dl_c)
            rank = jnp.cumsum(survivor_ok.astype(jnp.int32))
            sel_c = survivor_ok & (rank <= m_cut)
            mask = jnp.zeros(w, jnp.float32).at[chosen].add(
                sel_c.astype(jnp.float32))
            if has_stale:
                cap_c = strag_branch | (dl_c & (dl <= s_max))
                d_c = jnp.where(strag_branch,
                                jnp.minimum(late_d[chosen], s_max),
                                jnp.minimum(dl, s_max))
                cap = jnp.zeros(w, jnp.float32).at[chosen].add(
                    jnp.where(cap_c, 1.0, 0.0))
                d_vec = jnp.zeros(w, jnp.int32).at[chosen].add(
                    jnp.where(cap_c, d_c, 0))
            else:
                cap = jnp.zeros(w, jnp.float32)
                d_vec = jnp.zeros(w, jnp.int32)
            return mask, cap, d_vec

        def chaos_block_fn(theta, params, mom, duals, c_global, streak,
                           until, st_admit, st_w, stale_p, m_cut, ts,
                           chosen, away, crashed, unreach, straggler,
                           up_drop, up_delay, late_d, limits,
                           corrupt_raw, idxs, bws, train_x, train_y, ex,
                           ey, ew, tidx, tweight, vidx, vw):
            def body(carry, xs):
                th, p, mo, d, c, stk, unt, sta, stw, sp = carry
                (t_t, ch, aw, cr, un, sg, ud, dl, ld, lim, craw, idx,
                 bw) = xs
                # Round start: readmit expired sentences (mirrors
                # _round_participation), then decide who plays.
                expired = (unt != 0) & (t_t >= unt)
                unt = jnp.where(expired, 0, unt)
                stk = jnp.where(expired, 0, stk)
                quar = unt > t_t
                kw = {}
                if has_stale:
                    due = (sta == t_t) & (stw > 0)
                    admit_w = jnp.where(due & ~quar, stw, 0.0)
                    sta = jnp.where(due, 0, sta)
                    stw = jnp.where(due, 0.0, stw)
                mask, cap, d_vec = device_participation(
                    t_t, ch, quar, aw, cr, un, sg, ud, dl, ld, m_cut)
                if has_stale:
                    captured = cap > 0
                    sta = jnp.where(captured, t_t + d_vec, sta)
                    stw = jnp.where(captured, decay_pow[d_vec], stw)
                    kw.update(load_mask=jnp.clip(mask + cap, 0.0, 1.0),
                              stale_p=sp, admit_w=admit_w, capture=cap)
                if has_corrupt:
                    kw["cmask"] = craw * jnp.clip(mask + cap, 0.0, 1.0)
                out = round_fn(th, p, mo, d, c, mask, lim, idx, bw,
                               train_x, train_y, ex, ey, ew, tidx,
                               tweight, vidx, vw, **kw)
                if has_stale:
                    th, p, mo, d, c, sp, packed = out
                else:
                    th, p, mo, d, c, packed = out
                # Screen feedback over the round's sampled lanes — the
                # jnp mirror of _apply_screen_feedback (packed layout:
                # the [W] screened flags start at offset 5).
                scr = packed[5:5 + w]
                part = mask > 0
                flagged = part & (scr > 0.5)
                stk2 = jnp.where(flagged, stk + 1,
                                 jnp.where(part, 0, stk))
                if q_on:
                    trigger = flagged & (stk2 >= q_after)
                    unt = jnp.where(trigger, t_t + 1 + q_rounds, unt)
                    stk = jnp.where(trigger, 0, stk2)
                else:
                    stk = stk2
                return (th, p, mo, d, c, stk, unt, sta, stw, sp), packed

            carry, packed = jax.lax.scan(
                body,
                (theta, params, mom, duals, c_global, streak, until,
                 st_admit, st_w, stale_p),
                (ts, chosen, away, crashed, unreach, straggler, up_drop,
                 up_delay, late_d, limits, corrupt_raw, idxs, bws))
            return (*carry, packed)

        self._chaos_block_fn = jax.jit(chaos_block_fn,
                                       donate_argnums=(1, 2, 3))

        # ---- population wave loop (hierarchical aggregation) ----------
        # One jitted dispatch per round: lax.scan over the cohort's
        # waves.  Each wave loads theta into all lanes (stateless
        # clients: fresh zero momentum), trains, injects the round's
        # client-keyed corruption, screens non-finite lanes, and folds
        # the valid lanes' updates into an f32 per-lane accumulator —
        # per-DEVICE partial sums, no cross-device traffic per wave.
        # After the scan, ONE bucketed reduce (masked_average_scatter
        # over the flat-tree spec, denom = total cohort weight) forms
        # theta.  Cohort size, survivor count and corruption are all
        # DATA ([K, lanes] masks), so every round of a population run
        # shares this single compiled program.
        if self._registry is not None:
            pop_lanes = self._registry.lanes
            pop_spec = self._pop_spec
            pop_clip = clip_radius

            def pop_round_fn(theta, idxs, bws, valids, limits, train_x,
                             train_y, ex, ey, ew, cmasks=None):
                acc0 = jax.tree.map(
                    lambda x: jnp.zeros((pop_lanes,) + x.shape,
                                        jnp.float32), theta)

                def wave(carry, xs):
                    acc, acc_w, lsum, asum = carry
                    if has_corrupt:
                        valid, cmask, lim, idx, bw = xs
                    else:
                        valid, lim, idx, bw = xs
                    start = broadcast_to_workers(theta, pop_lanes)
                    mom0 = jax.tree.map(jnp.zeros_like, start)
                    with jax.named_scope("dopt_local"):
                        bx, by = gather_rows(train_x, train_y, idx)
                        args = ((start, mom0, bx, by, bw, lim)
                                if may_straggle
                                else (start, mom0, bx, by, bw))
                        if algorithm == "fedprox":
                            p_t, _m_t, losses, accs = local(*args, theta)
                        else:
                            p_t, _m_t, losses, accs = local(*args)
                    if has_corrupt:
                        # Client-keyed lies: the [lanes] mask is the
                        # population fault stream gathered at this
                        # wave's client ids, so a pinned adversary lies
                        # in every cohort that samples it.
                        p_t = corrupt_update(p_t, cmask, corrupt_mode,
                                             corrupt_scale, ref=theta,
                                             prev=start)
                    fin = finite_lane_mask(p_t) * valid
                    agg_in = (clip_to_ball(p_t, theta, pop_clip)
                              if pop_clip > 0 else p_t)
                    # Zero screened/padding lanes BEFORE accumulating:
                    # a 0-weighted NaN still poisons the sum.
                    zed = _where_mask(
                        fin, agg_in,
                        jax.tree.map(jnp.zeros_like, agg_in))
                    with jax.named_scope("dopt_mix"):
                        acc = jax.tree.map(
                            lambda a, x: a + x.astype(jnp.float32), acc,
                            zed)
                    acc_w = acc_w + fin
                    lane_loss = losses.mean(axis=1)
                    lane_loss = jnp.where(jnp.isfinite(lane_loss),
                                          lane_loss, 0.0)
                    lane_acc = accs.mean(axis=1)
                    lane_acc = jnp.where(jnp.isfinite(lane_acc),
                                         lane_acc, 0.0)
                    lsum = lsum + (lane_loss * fin).sum()
                    asum = asum + (lane_acc * fin).sum()
                    screened = valid * (1.0 - finite_lane_mask(p_t))
                    return (acc, acc_w, lsum, asum), screened

                xs = [valids]
                if has_corrupt:
                    xs.append(cmasks)
                xs += [limits, idxs, bws]
                (acc, acc_w, lsum, asum), scr = jax.lax.scan(
                    wave,
                    (acc0, jnp.zeros(pop_lanes, jnp.float32),
                     jnp.float32(0.0), jnp.float32(0.0)),
                    tuple(xs))
                tot = acc_w.sum()
                denom = jnp.where(tot > 0, tot, 1.0)
                avg = masked_average_scatter(
                    acc, jnp.ones(pop_lanes, jnp.float32), agg_mesh,
                    pop_spec, denom=denom)
                # Empty round (everyone crashed/quarantined): theta
                # passes through, like the lane engines' all-failed
                # guard.
                new_theta = jax.tree.map(
                    lambda a, th: jnp.where(tot > 0, a.astype(th.dtype),
                                            th),
                    avg, theta)
                cnt = jnp.maximum(tot, 1.0)
                evalm = global_eval(new_theta, ex, ey, ew)
                # Packed host metrics (one fetch): [local_loss,
                # test_acc, test_loss_sum, train_loss, train_acc] +
                # [K·lanes] screened flags.  train_loss/train_acc are
                # the COHORT's local-training means — the all-client
                # train eval has no population-scale analog.
                parts = [(lsum / cnt)[None], evalm["acc"][None],
                         evalm["loss_sum"][None], (lsum / cnt)[None],
                         (asum / cnt)[None], scr.ravel()]
                packed = jnp.concatenate(
                    [p.astype(jnp.float32) for p in parts])
                return new_theta, packed

            self._pop_round_fn = jax.jit(pop_round_fn)
            from dopt.parallel.mesh import worker_axes as _wa

            self._pop_sharding = jax.sharding.NamedSharding(
                self.mesh,
                jax.sharding.PartitionSpec(None, _wa(self.mesh)))

        self._global_eval = jax.jit(global_eval)
        self._sample_rng = host_rng(cfg.seed, 314159)

    # ------------------------------------------------------------------
    def _sample_indices(self, frac: float) -> np.ndarray:
        """m = max(int(frac*N), 1) clients without replacement
        (servers.py:52,57), as sorted indices."""
        m = max(int(frac * self.num_workers), 1)
        chosen = self._sample_rng.choice(self.num_workers, m, replace=False)
        return np.sort(chosen).astype(np.int32)

    def sample_clients(self, frac: float) -> np.ndarray:
        """Client sample as a 0/1 mask over the worker axis."""
        mask = np.zeros(self.num_workers, np.float32)
        mask[self._sample_indices(frac)] = 1.0
        return mask

    def _participation_static(self, t: int, frac: float) -> dict:
        """Carry-INDEPENDENT per-round participation inputs for the
        fused chaos block: the candidate draw (the only stateful step —
        same RNG call, same stream as the per-round path) plus the
        round's stateless fault vectors, as [W] device-ready arrays.
        Touches NO quarantine/staleness state and emits NO ledger rows;
        the blocked loop replays ``_round_participation(t, frac,
        chosen=...)`` post-fetch once the screened flags are back."""
        w = self.num_workers
        m = max(int(frac * w), 1)
        c = self.faults.cfg
        n_draw = m
        if self.faults.active and c.over_select > 0.0:
            n_draw = min(int(np.ceil(m * (1.0 + c.over_select))), w)
        chosen = self._sample_rng.choice(
            w, n_draw, replace=False).astype(np.int32)
        rf = self.faults.for_round(t)
        away = self.faults.away_for_round(t)
        up_drop, up_delay = self.faults.uplink_for_round(t)
        unreach = (np.zeros(w, bool) if rf.partition is None
                   else rf.partition != 0)
        late_d = (self.faults.straggler_lateness(t, self._staleness_max)
                  if self._has_stale else np.zeros(w, np.int32))
        corrupt = (rf.corrupt
                   if self._has_corrupt and rf.corrupt is not None
                   else np.zeros(w, bool))
        return dict(
            chosen=chosen, away=away.astype(np.float32),
            crashed=rf.crashed.astype(np.float32),
            unreach=unreach.astype(np.float32),
            straggler=rf.straggler.astype(np.float32),
            up_drop=up_drop.astype(np.float32),
            up_delay=up_delay.astype(np.int32),
            late_d=late_d.astype(np.int32),
            limits=FaultPlan.limits_for(rf, self._straggle_units),
            corrupt=corrupt.astype(np.float32))

    def _round_participation(
            self, t: int, frac: float, chosen: np.ndarray | None = None
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray, list,
               np.ndarray, np.ndarray]:
        """Sample round t's clients and apply its faults: returns
        (survivor indices, [W] straggler work limits, [W] corrupt mask,
        the round's host-side fault-ledger rows, [W] capture mask,
        [W] admission weights).

        Fault-free this is exactly ``_sample_indices`` (same RNG call,
        same stream — enabling the fault machinery never perturbs the
        sampling sequence).  With faults on, the FedAvg-paper server
        deadline runs on the host: over-select ceil(m·(1+over_select))
        clients, drop the quarantined / away / crashed /
        partition-unreachable / uplink-faulted / deadline-dropped ones,
        keep the first m survivors and release the surplus.  Under
        staleness-aware aggregation, deadline-missed stragglers and
        delayed uplinks are CAPTURED (capture mask) instead of dropped
        and their buffered updates ADMITTED d rounds later (admission
        weights carry staleness_decay^d).  Ledger rows are RETURNED
        rather than appended so both execution paths (per-round and
        fused-block) can interleave them with the device-side screened
        rows in the identical order — draws are stateless per round
        (dopt.faults.FaultPlan), so per-round, blocked, and
        killed-and-resumed execution log the identical trace."""
        rows: list[dict] = []
        w = self.num_workers
        capture = np.zeros(w, np.float32)
        admit_w = np.zeros(w, np.float32)
        if self._quarantine_on:
            expired = ((self._quarantine_until != 0)
                       & (t >= self._quarantine_until))
            for i in np.nonzero(expired)[0]:
                rows.append({"round": int(t), "worker": int(i),
                             "kind": "quarantine", "action": "readmitted"})
                self._quarantine_until[i] = 0
                self._screen_streak[i] = 0
        if self._has_stale:
            # Admissions due this round: buffered late updates enter the
            # aggregate at their decay weight — unless their sender was
            # quarantined meanwhile (composition with the Byzantine
            # detection layer: a benched worker's pending work is
            # distrusted wholesale).
            due = (self._stale_admit_round == t) & (self._stale_weight > 0)
            for i in np.nonzero(due)[0]:
                if (self._quarantine_on
                        and t < self._quarantine_until[i]):
                    rows.append({"round": int(t), "worker": int(i),
                                 "kind": "staleness",
                                 "action": "dropped_quarantined"})
                else:
                    admit_w[i] = np.float32(self._stale_weight[i])
                    d = int(t - self._stale_origin[i])
                    rows.append({"round": int(t), "worker": int(i),
                                 "kind": "staleness",
                                 "action": f"admitted_after_{d}_rounds"})
                self._stale_admit_round[i] = 0
                self._stale_weight[i] = 0.0
        away = self.faults.away_for_round(t)
        if self.faults.has_churn:
            rows.extend(churn_ledger_rows(self.faults, t, away))
        m = max(int(frac * w), 1)
        c = self.faults.cfg
        n_draw = m
        if self.faults.active and c.over_select > 0.0:
            n_draw = min(int(np.ceil(m * (1.0 + c.over_select))), w)
        # Keep the RNG's DRAW order for the survivor cut below: the
        # over-selection surplus must be released uniformly (sorting
        # first would systematically release the highest worker ids,
        # biasing participation toward low ids); the final survivor
        # set is sorted on return.  ``chosen`` can be supplied by the
        # fused-chaos blocked loop, whose plan phase already drew it
        # (``_participation_static``) — the replay must not re-draw.
        if chosen is None:
            chosen = self._sample_rng.choice(
                w, n_draw, replace=False).astype(np.int32)
        rf = self.faults.for_round(t)
        limits = FaultPlan.limits_for(rf, self._straggle_units)
        cmask = np.zeros(w, np.float32)
        up_drop, up_delay = self.faults.uplink_for_round(t)
        quarantined_now = (self._quarantine_on
                           and bool((self._quarantine_until > t).any()))
        if (not rf.any_fault and n_draw == m and not quarantined_now
                and not away.any() and not up_drop.any()
                and not up_delay.any() and not admit_w.any()):
            return np.sort(chosen), limits, cmask, rows, capture, admit_w
        drop_policy = c is not None and c.straggler_policy == "drop"
        late_d = (self.faults.straggler_lateness(t, self._staleness_max)
                  if self._has_stale else None)
        survivors: list[int] = []
        captured: list[int] = []

        def _capture(i: int, d: int) -> None:
            d = min(int(d), self._staleness_max)
            if self._stale_admit_round[i] > t:
                rows.append({"round": int(t), "worker": i,
                             "kind": "staleness",
                             "action": "pending_overwritten"})
            capture[i] = 1.0
            captured.append(i)
            self._stale_admit_round[i] = t + d
            self._stale_weight[i] = float(self._staleness_decay) ** d
            self._stale_origin[i] = t

        for i in chosen:
            i = int(i)
            if quarantined_now and t < self._quarantine_until[i]:
                rows.append({"round": int(t), "worker": i,
                             "kind": "quarantine",
                             "action": "excluded_while_quarantined"})
            elif away[i]:
                rows.append({"round": int(t), "worker": i, "kind": "churn",
                             "action": "excluded_while_away"})
            elif rf.crashed[i]:
                rows.append({"round": int(t), "worker": i, "kind": "crash",
                             "action": "dropped_from_round"})
            elif rf.partition is not None and rf.partition[i] != 0:
                # Only group 0 can reach the server for the span.
                rows.append({
                    "round": int(t), "worker": i, "kind": "partition",
                    "action": f"unreachable_in_group_{int(rf.partition[i])}"})
            elif rf.straggler[i] and drop_policy:
                if self._has_stale:
                    # Staleness-aware: the straggler finishes its FULL
                    # local work and its update arrives d rounds late
                    # (under policy='drop' the device compiles
                    # with_limit=False, so the limits vector is never
                    # applied — no truncation to undo here).
                    d = min(int(late_d[i]), self._staleness_max)
                    rows.append({
                        "round": int(t), "worker": i, "kind": "straggler",
                        "action": f"deadline_buffered_arriving_{t + d}"})
                    _capture(i, d)
                else:
                    # Audit-complete hard drop: record the step budget
                    # the straggler actually executed before the server
                    # deadline (the with_limit value), not just the
                    # deadline action.
                    rows.append({
                        "round": int(t), "worker": i, "kind": "straggler",
                        "action": (f"deadline_dropped_after_"
                                   f"{int(limits[i])}_of_"
                                   f"{self._straggle_units}")})
            elif up_drop[i]:
                rows.append({"round": int(t), "worker": i,
                             "kind": "msg_drop", "action": "uplink_dropped"})
            elif up_delay[i] > 0:
                d = int(up_delay[i])
                if self._has_stale and d <= self._staleness_max:
                    rows.append({"round": int(t), "worker": i,
                                 "kind": "msg_delay",
                                 "action": f"uplink_buffered_delay_{d}"})
                    _capture(i, d)
                else:
                    rows.append({"round": int(t), "worker": i,
                                 "kind": "msg_delay",
                                 "action": f"uplink_dropped_stale_{d}"})
            else:
                survivors.append(i)
        for i in survivors[m:]:
            rows.append({"round": int(t), "worker": i, "kind": "overselect",
                         "action": "released_surplus"})
        survivors = np.sort(np.asarray(survivors[:m], np.int32))
        if self._may_straggle:
            for i in survivors:
                if rf.straggler[i]:
                    rows.append({
                        "round": int(t), "worker": int(i),
                        "kind": "straggler",
                        "action": (f"truncated_to_{int(limits[i])}"
                                   f"_of_{self._straggle_units}")})
        if self._has_corrupt and rf.corrupt is not None:
            mode = self.cfg.faults.corrupt_mode
            # A liar lies on the late channel too: captured updates are
            # corrupted under the same mask as fresh ones.
            for i in sorted(set(survivors.tolist()) | set(captured)):
                if rf.corrupt[i]:
                    cmask[i] = 1.0
                    rows.append({"round": int(t), "worker": int(i),
                                 "kind": "corrupt",
                                 "action": f"injected_{mode}"})
        return survivors, limits, cmask, rows, capture, admit_w

    def _apply_screen_feedback(self, t: int, workers, flags,
                               rows: list) -> None:
        """Fold the device step's non-finite-screen flags (aligned with
        ``workers``, the round's surviving sampled clients) into the
        ledger and the quarantine streaks: K consecutive screened
        participations quarantine the worker for ``quarantine_rounds``;
        one clean participation resets the streak."""
        for j, wid in enumerate(np.asarray(workers).reshape(-1)):
            wid = int(wid)
            if float(flags[j]) > 0.5:
                self._screen_streak[wid] += 1
                rows.append({"round": int(t), "worker": wid,
                             "kind": "corrupt",
                             "action": "screened_nonfinite"})
                if (self._quarantine_on and self._screen_streak[wid]
                        >= self._quarantine_after):
                    until = int(t) + 1 + self._quarantine_rounds
                    self._quarantine_until[wid] = until
                    self._screen_streak[wid] = 0
                    rows.append({"round": int(t), "worker": wid,
                                 "kind": "quarantine",
                                 "action": f"quarantined_until_{until}"})
            else:
                self._screen_streak[wid] = 0

    def _use_compact(self, frac: float) -> bool:
        f = self.cfg.federated
        if self._scatter:
            # The sharded-update reduce is a full-width collective over
            # the worker axis; compact's gathered-lane mean has nothing
            # to shard (explicit compact=True was rejected at init).
            return False
        if self._fused_on:
            # The fused epilogue contracts the full [W, ...] slab —
            # compact's gathered-lane mean has nothing to contract
            # (explicit compact=True was rejected at init).
            return False
        if self._has_stale:
            # The staleness path needs full-width lanes: captured late
            # senders train outside the aggregating sample, and the
            # one-slot-per-worker buffer is a [W, ...] scatter target.
            if f.compact:
                raise ValueError(
                    "FederatedConfig.compact=True is incompatible with "
                    "staleness-aware aggregation (captured lanes train "
                    "outside the sampled set) — drop one of the two")
            return False
        if f.comm_dtype:
            # The compact path's aggregation is a local mean over m
            # lanes — no cross-worker collective to compress — so the
            # knob would silently not apply; force full-width (and
            # reject an explicit compact=True request).
            if f.compact:
                raise ValueError(
                    "FederatedConfig.compact=True is incompatible with "
                    "comm_dtype (the compact path has no cross-worker "
                    "collective to compress)")
            return False
        if self.mesh.size > 1:
            # The compact path re-shapes the worker axis to m lanes and
            # never applies the mesh sharding — single-device only; on a
            # sharded mesh the N lanes are parallel hardware, so the
            # full-width path is the right one anyway.  Checked before
            # any frac-dependent early-out so an invalid config is
            # rejected consistently, whatever frac this run uses.
            if f.compact:
                raise ValueError(
                    "FederatedConfig.compact=True requires a single-device "
                    f"mesh (have {self.mesh.size} devices)")
            return False
        m = max(int(frac * self.num_workers), 1)
        if m >= self.num_workers:
            return False
        if f.compact is not None:
            return f.compact
        return True

    def _fixed_width_sel(self, sel: np.ndarray,
                         frac: float) -> tuple[np.ndarray, np.ndarray]:
        """Pad a round's survivor set to the static m = max(frac·W, 1)
        lane count: survivors first, then deterministic padding ids
        (the lowest worker ids not already selected), with a 0/1
        validity prefix mask.  Padding lanes train and are discarded by
        the validity mask — survivor counts become DATA, so every
        faulted compact round shares one compiled program and stacks
        into fused blocks."""
        w = self.num_workers
        m = max(int(frac * w), 1)
        pad = np.setdiff1d(np.arange(w, dtype=np.int32),
                           sel)[:m - len(sel)]
        sel_full = np.concatenate([sel, pad]).astype(np.int32)
        valid = np.zeros(m, np.float32)
        valid[:len(sel)] = 1.0
        return sel_full, valid

    # -- population mode (dopt.population) -----------------------------
    def _cohort_participation(self, t: int):
        """Sample round t's cohort from the population and apply its
        CLIENT-keyed faults: returns (binding, [K, lanes] straggler
        limits, [K, lanes] corrupt mask, ledger rows).  The priority
        chain mirrors ``_round_participation`` (quarantine > churn >
        crash > partition > deadline > uplink) except that quarantine
        and churn exclude clients at SAMPLING time (the registry's
        eligibility mask) and there is no staleness buffer — a delayed
        uplink is dropped like the staleness_max=0 lane path.  Every
        draw is stateless per (seed, round), so per-round execution and
        killed-and-resumed runs log the identical trace.  NOTE: the
        chain is a deliberate simplified twin of
        ``_round_participation`` (whose staleness-capture branches and
        exact ledger ordering are load-bearing there) — a change to
        either chain's actions or priorities must be mirrored in the
        other."""
        reg = self._registry
        rows = reg.begin_round(t)
        away = reg.faults.away_for_round(t)
        if reg.faults.has_churn:
            # Population-keyed churn rows (client leave/rejoin + true
            # orphan-SHARD adoptions) — the worker-level
            # churn_ledger_rows assumes worker i owns shard i.
            rows.extend(reg.churn_ledger_rows(t, away))
        eligible = ~(reg.quarantine_until > t) & ~away
        c = reg.faults.cfg
        m = reg.cohort_size
        n_draw = m
        if reg.faults.active and c.over_select > 0.0:
            n_draw = int(np.ceil(m * (1.0 + c.over_select)))
        cohort = reg.sample_cohort(t, n_draw=n_draw, eligible=eligible)
        binding_row_at = len(rows)
        rf = reg.faults.for_round(t)
        limits_p = FaultPlan.limits_for(rf, self._straggle_units)
        up_drop, up_delay = reg.faults.uplink_for_round(t)
        drop_policy = c is not None and c.straggler_policy == "drop"
        survivors: list[int] = []
        for i in cohort:
            i = int(i)
            if rf.crashed[i]:
                rows.append({"round": int(t), "worker": i, "kind": "crash",
                             "action": "dropped_from_round"})
            elif rf.partition is not None and rf.partition[i] != 0:
                rows.append({
                    "round": int(t), "worker": i, "kind": "partition",
                    "action": f"unreachable_in_group_{int(rf.partition[i])}"})
            elif rf.straggler[i] and drop_policy:
                rows.append({
                    "round": int(t), "worker": i, "kind": "straggler",
                    "action": (f"deadline_dropped_after_{int(limits_p[i])}"
                               f"_of_{self._straggle_units}")})
            elif up_drop[i]:
                rows.append({"round": int(t), "worker": i,
                             "kind": "msg_drop", "action": "uplink_dropped"})
            elif up_delay[i] > 0:
                rows.append({"round": int(t), "worker": i,
                             "kind": "msg_delay",
                             "action": f"uplink_dropped_stale_"
                                       f"{int(up_delay[i])}"})
            else:
                survivors.append(i)
        for i in survivors[m:]:
            rows.append({"round": int(t), "worker": i, "kind": "overselect",
                         "action": "released_surplus"})
        survivors_a = np.asarray(survivors[:m], np.int64)
        binding = reg.bind(t, cohort, survivors_a)
        rows.insert(binding_row_at, binding.ledger_row(reg.clients))
        if self._may_straggle:
            for i in np.sort(survivors_a):
                if rf.straggler[i]:
                    rows.append({
                        "round": int(t), "worker": int(i),
                        "kind": "straggler",
                        "action": (f"truncated_to_{int(limits_p[i])}"
                                   f"_of_{self._straggle_units}")})
        limits = limits_p[binding.lane_ids]
        cmask = np.zeros((binding.waves, binding.lanes), np.float32)
        if self._has_corrupt and rf.corrupt is not None:
            cmask = (rf.corrupt[binding.lane_ids].astype(np.float32)
                     * binding.valid)
            mode = self.cfg.faults.corrupt_mode
            for i in np.sort(survivors_a):
                if rf.corrupt[i]:
                    rows.append({"round": int(t), "worker": int(i),
                                 "kind": "corrupt",
                                 "action": f"injected_{mode}"})
        # NOTE: participation is recorded at the loop's post-fetch
        # COMMIT point (next to the screen feedback), not here: the
        # prefetched loop draws round t+1's cohort before round t's
        # commit, and the registry counters the telemetry gauges read
        # must reflect only committed rounds on both paths.  Sampling
        # itself never reads the counters, so the move is unobservable
        # to the draw.
        return binding, limits, cmask, rows

    def _draw_pop_round(self, t: int) -> dict:
        """Stateful half of one population round's staging: the cohort
        participation chain (registry eligibility reads + the fault
        draws).  Main thread, round order (prefetch ordering
        contract)."""
        binding, limits, cmask, rows = self._cohort_participation(t)
        return {"t": t, "binding": binding, "rows": rows,
                "cmask": cmask, "valids": jnp.asarray(binding.valid),
                "lim": jnp.asarray(limits)}

    def _build_pop_round(self, meta: dict) -> dict:
        """Pure half: the K wave plans + their device staging (safe on
        the stager thread — every input is stateless in the round)."""
        cfg, f, reg = self.cfg, self.cfg.federated, self._registry
        t, binding = meta["t"], meta["binding"]
        pm = reg.plan_matrix_for(t, self._train_matrix)
        plans = [
            make_batch_plan(
                pm, batch_size=f.local_bs, local_ep=f.local_ep,
                seed=cfg.seed, round_idx=t,
                impl=cfg.data.plan_impl,
                workers=binding.lane_ids[k],
                rows=reg.shard_of[binding.lane_ids[k]])
            for k in range(binding.waves)
        ]
        meta["idx"] = jax.device_put(np.stack([p.idx for p in plans]),
                                     self._pop_sharding)
        meta["bw"] = jax.device_put(np.stack([p.weight for p in plans]),
                                    self._pop_sharding)
        return meta

    def _population_path(self) -> RoundPath:
        """Population mode: one jitted wave-scan dispatch per round (the
        K-wave scan already amortises dispatch the way blocked
        execution does for the lane engines; cohort size never
        retraces).  With ``prefetch='on'`` round t+1's cohort is drawn
        (main thread) and its wave plans built/staged (background
        thread) while round t runs; participation is committed
        post-fetch, and client quarantine was rejected at construction
        (its eligibility feedback only exists after the fetch)."""
        reg = self._registry

        def launch(payload):
            step_kw = ({"cmasks": jnp.asarray(payload["cmask"])}
                       if self._has_corrupt else {})
            args = (self.theta, payload["idx"], payload["bw"],
                    payload["valids"], payload["lim"], self._train_x,
                    self._train_y, *self._eval)
            return "pop_round_fn", self._pop_round_fn, args, step_kw

        def commit(out):
            self.theta, packed = out
            return packed

        def record(payload, packed):
            t, binding, rows = (payload["t"], payload["binding"],
                                payload["rows"])
            ll, acc, loss_sum, t_loss, t_acc = (float(v)
                                                for v in packed[:5])
            n = len(binding.survivors)
            # COMMIT: the registry counters advance only here, post-
            # fetch — identical state at every observable point
            # (gauges, checkpoints) on both the prefetched and the
            # unprefetched path.
            reg.record_participation(t, binding.survivors)
            # Survivors occupy the first n wave-major slots; padding
            # lanes' flags are discarded like compact padding lanes'.
            flags = packed[5:].reshape(-1)[:n]
            reg.apply_screen_feedback(t, binding.survivors, flags, rows)
            self.history.faults.extend(rows)
            self.history.append(
                round=t,
                test_acc=acc,
                test_loss=loss_sum,  # P1 summed-loss flavour
                train_loss=t_loss,
                train_acc=t_acc,
                local_loss=ll,
                cohort=n,
                population=reg.clients,
            )
            self._round_telemetry(t, rows)
            self.round += 1

        return RoundPath(draw=lambda ts: self._draw_pop_round(ts[0]),
                         build=self._build_pop_round, launch=launch,
                         commit=commit, record=record,
                         prefetch=self._prefetch)

    def _blocked_path(self, frac: float, block: int) -> RoundPath:
        """The path that fuses up to ``block`` rounds into one scan.
        Compact + faults runs fixed-width validity-masked lanes
        (survivor counts are data, not shapes).  With ``prefetch='on'``
        the next block's participation draws stay on the main thread
        (in block order, so the sampling stream is byte-identical) and
        its plan build + device staging overlap the current block's
        device time."""
        compact = self._use_compact(frac)
        fixed_c = compact and self.faults.active
        fn_name = ("compact_fault_block_fn" if fixed_c
                   else "compact_block_fn" if compact else "block_fn")
        fn = getattr(self, "_" + fn_name)

        def launch(payload):
            step_kw = {}
            if self._has_corrupt:
                step_kw["cmasks"] = payload["cms"]
            if fixed_c:
                step_kw["valids"] = payload["valids"]
            args = (self.theta, self.params, self.momentum,
                    *self._dual_inputs(), payload["gates"],
                    payload["limits"], payload["idx"], payload["bw"],
                    self._train_x, self._train_y, *self._eval,
                    self._train_eval_idx, self._train_eval_w, *self._val)
            return fn_name, fn, args, step_kw

        def record(payload, packed):
            lanes = len(payload["lane_sels"][0]) if compact else None
            for j, t in enumerate(payload["ts"]):
                self._record_round(t, packed[j], payload["sels"][j],
                                   payload["frows"][j], lanes)

        return RoundPath(
            draw=lambda ts: self._draw_block(ts, frac, compact, fixed_c),
            build=self._build_block, launch=launch, commit=self._commit,
            record=record, block=block, prefetch=self._prefetch)

    def _draw_block(self, ts: list, frac: float, compact: bool,
                    fixed_c: bool) -> dict:
        """Stateful half of one plain-blocked block's staging: the
        participation draws (the client-sampling RNG stream advances
        here, in block order — the prefetch ordering contract)."""
        parts = [self._round_participation(t, frac) for t in ts]
        sels = [p[0] for p in parts]
        frows = [p[3] for p in parts]
        if fixed_c:
            fw = [self._fixed_width_sel(sel, frac) for sel in sels]
            lane_sels = [x[0] for x in fw]
            valids = jnp.asarray(np.stack([x[1] for x in fw]))
        else:
            lane_sels = sels
            valids = None
        if self._has_corrupt:
            # [k, lanes] corrupt masks: full-width rounds stack the [W]
            # masks directly, fixed-width compact rounds gather their
            # lane slice (padding ids carry no lie — the host only
            # flags survivors/captured).
            cms = jnp.asarray(np.stack(
                [p[2][ls] for p, ls in zip(parts, lane_sels)]
                if compact else [p[2] for p in parts]))
        else:
            cms = None
        if compact:
            gates = jnp.asarray(np.stack(lane_sels))
            limits = jnp.asarray(np.stack(
                [p[1][ls] for ls, p in zip(lane_sels, parts)]))
        else:
            masks = np.zeros((len(ts), self.num_workers), np.float32)
            for j, sel in enumerate(sels):
                masks[j, sel] = 1.0
            gates = jnp.asarray(masks)
            limits = jnp.asarray(np.stack([p[1] for p in parts]))
        return {"ts": ts, "compact": compact, "sels": sels,
                "frows": frows, "lane_sels": lane_sels, "valids": valids,
                "cms": cms, "gates": gates, "limits": limits}

    def _build_block(self, meta: dict) -> dict:
        """Pure half: the block's batch plans + device staging (safe on
        the stager thread)."""
        cfg, f = self.cfg, self.cfg.federated
        ts, compact = meta["ts"], meta["compact"]
        plans = [
            make_batch_plan(
                self._plan_matrix_for_round(t), batch_size=f.local_bs,
                local_ep=f.local_ep, seed=cfg.seed, round_idx=t,
                impl=cfg.data.plan_impl,
                workers=lane_sel if compact else None,
            )
            for t, lane_sel in zip(ts, meta["lane_sels"])
        ]
        if compact:
            meta["idx"] = jnp.asarray(np.stack([p.idx for p in plans]))
            meta["bw"] = jnp.asarray(np.stack([p.weight for p in plans]))
        else:
            block_sharding = jax.sharding.NamedSharding(
                self.mesh,
                jax.sharding.PartitionSpec(None, worker_axes(self.mesh)))
            meta["idx"] = jax.device_put(
                np.stack([p.idx for p in plans]), block_sharding)
            meta["bw"] = jax.device_put(
                np.stack([p.weight for p in plans]), block_sharding)
        return meta

    def _chaos_path(self, frac: float, block: int) -> RoundPath:
        """Fused blocked execution for the modes whose round-to-round
        state used to pin them per-round: quarantine (streak/until) and
        staleness-aware aggregation (admission schedule + the one-slot
        late-update buffer) ride the scan CARRY, participation is
        decided on device from the pre-drawn candidate lists, and the
        host replays the identical integer logic post-fetch so the
        ledger rows (and their order) are bit-identical to per-round
        execution.  Both modes force the full-width path (``run`` keeps
        compact+quarantine per-round; staleness rejects compact)."""
        m = max(int(frac * self.num_workers), 1)
        # The scan's final streak/until/admission carry, held from
        # ``commit`` to ``record``'s check against the host replay.
        dev_carry: list = []

        def launch(payload):
            stacks = payload["stacks"]
            # The carry inputs (streaks, admission schedule) are read
            # HERE, at dispatch time, after the previous block's
            # replay — only the plan payload is staged ahead.
            args = (self.theta, self.params, self.momentum,
                    *self._dual_inputs(),
                    jnp.asarray(self._screen_streak.astype(np.int32)),
                    jnp.asarray(self._quarantine_until.astype(np.int32)),
                    jnp.asarray(self._stale_admit_round.astype(np.int32)),
                    jnp.asarray(self._stale_weight.astype(np.float32)),
                    self._stale_p if self._has_stale else {},
                    jnp.asarray(m, jnp.int32),
                    jnp.asarray(payload["ts"], jnp.int32),
                    jnp.asarray(payload["chosen"]),
                    stacks["away"], stacks["crashed"], stacks["unreach"],
                    stacks["straggler"], stacks["up_drop"],
                    stacks["up_delay"], stacks["late_d"],
                    stacks["limits"], stacks["corrupt"], payload["idx"],
                    payload["bw"], self._train_x, self._train_y,
                    *self._eval,
                    self._train_eval_idx, self._train_eval_w,
                    *self._val)
            return "chaos_block_fn", self._chaos_block_fn, args, {}

        def commit(out):
            dev_carry[:] = out[5:9]
            return self._commit(out)

        def record(payload, packed):
            for j, t in enumerate(payload["ts"]):
                # Post-fetch ledger replay: host quarantine/staleness
                # mirrors are current through round t-1's flags, so
                # this regenerates exactly the per-round path's rows —
                # and the same candidate draw is reused, not re-drawn.
                (sel, _lim, _cm, frows, _cap,
                 _admit) = self._round_participation(
                     t, frac, chosen=payload["chosen"][j])
                self._record_round(t, packed[j], sel, frows)
            # The host replay and the device carry apply the same rule
            # to the same flags; drift is a bug, surfaced loudly.
            dev_stk, dev_unt, dev_sta, dev_stw = dev_carry
            ok = (np.array_equal(np.asarray(dev_stk),
                                 self._screen_streak.astype(np.int32))
                  and np.array_equal(np.asarray(dev_unt),
                                     self._quarantine_until.astype(np.int32)))
            if self._has_stale:
                ok = ok and np.array_equal(
                    np.asarray(dev_sta),
                    self._stale_admit_round.astype(np.int32))
                ok = ok and np.array_equal(
                    np.asarray(dev_stw),
                    self._stale_weight.astype(np.float32))
            if not ok:
                raise RuntimeError(
                    "fused-chaos host replay diverged from the device "
                    "scan carry")

        return RoundPath(
            draw=lambda ts: self._draw_chaos_block(ts, frac),
            build=self._build_block, launch=launch, commit=commit,
            record=record, block=block, prefetch=self._prefetch)

    def _draw_chaos_block(self, ts: list, frac: float) -> dict:
        """Stateful half of one chaos block's staging: the candidate
        draws (sampling RNG, in block order) + the stateless per-round
        fault vectors.  Touches no quarantine/staleness state
        (``_participation_static``'s contract), so drawing block b+1
        before block b's post-fetch replay is exact."""
        stat = [self._participation_static(t, frac) for t in ts]
        return {"ts": ts, "compact": False,
                "lane_sels": [None] * len(ts),
                "chosen": np.stack([s["chosen"] for s in stat]),
                "stacks": {key: jnp.asarray(
                               np.stack([s[key] for s in stat]))
                           for key in ("away", "crashed", "unreach",
                                       "straggler", "up_drop",
                                       "up_delay", "late_d", "limits",
                                       "corrupt")}}

    def run(self, frac: float | None = None, rounds: int | None = None,
            block: int | None = None, checkpoint_every: int = 0,
            checkpoint_path=None) -> History:
        """Train; ``block`` (default ``cfg.federated.block_rounds``) > 1
        fuses that many rounds into one jit dispatch — same math, same
        per-round eval cadence, same client-sampling sequence; only the
        host/device round-trip count changes.

        ``checkpoint_every=K`` (with ``checkpoint_path``) auto-saves a
        full checkpoint every K rounds; a run killed at any point and
        resumed from the latest checkpoint is bit-identical to a
        continuous run (stateless fault/batch streams + persisted
        sampling-RNG state)."""
        f = self.cfg.federated
        frac = f.frac if frac is None else frac
        rounds = f.rounds if rounds is None else rounds
        block = f.block_rounds if block is None else block
        if checkpoint_every and checkpoint_path is None:
            raise ValueError("checkpoint_every requires checkpoint_path")
        if self._registry is not None:
            # Population mode: frac/block are lane-engine knobs — the
            # cohort size comes from the registry, and each round is
            # already one fused wave-scan dispatch.
            path = self._population_path()
        elif block > 1 and not (self._quarantine_on
                                and self._use_compact(frac)):
            # Every mode but compact+quarantine is blocked-eligible:
            # quarantine / staleness-aware runs fuse through the chaos
            # scan, whose carry holds their round-to-round state.
            # Compact+quarantine stays per-round: its gather indices
            # are host data but depend on the device-side quarantine
            # state.
            path = (self._chaos_path(frac, block)
                    if self._quarantine_on or self._has_stale
                    else self._blocked_path(frac, block))
        else:
            path = self._round_path(frac)
        return self._run_loop(path, rounds, checkpoint_every,
                              checkpoint_path)

    def _round_path(self, frac: float) -> RoundPath:
        """The per-round path: the payload is ``_round_dispatch``'s
        tuple, the ONE builder ``lower_round`` also consumes.  It reads
        the carried state, so nothing of it can be staged ahead."""

        def record(dispatch, packed):
            sel, sel_lanes, use_c, frows = dispatch[4:]
            self._record_round(self.round, packed, sel, frows,
                               len(sel_lanes) if use_c else None)

        return RoundPath(
            draw=lambda ts: self._round_dispatch(ts[0], frac),
            launch=lambda dispatch: dispatch[:4], commit=self._commit,
            record=record)

    def _dual_inputs(self) -> tuple:
        """(duals, c_global) as a lane round program takes them: an
        algorithm without one passes an empty tree."""
        return (self.duals if self.duals is not None else {},
                self.c_global if self.c_global is not None else {})

    def _commit(self, out):
        """Assign the lane engines' carried state from a round
        program's result — per-round, blocked or chaos: theta, params,
        momentum, duals and c_global lead, the packed metrics are last
        and a staleness run's late-update buffer sits just before
        them — and return the packed metrics."""
        self.theta, self.params, self.momentum, new_duals, new_c = out[:5]
        if self.duals is not None:
            self.duals = new_duals
        if self.c_global is not None:
            self.c_global = new_c
        if self._has_stale:
            self._stale_p = out[-2]
        return out[-1]

    def _record_round(self, t: int, vec: np.ndarray, sel, frows: list,
                      lanes: int | None = None) -> None:
        """Round ``t``'s host record from its fetched metrics vector:
        screen feedback of the surviving sampled clients ``sel`` into
        the ledger rows and quarantine streaks, the history row, the
        client rows, the telemetry bundle; advances ``self.round``.
        ``lanes`` is a compact program's lane count — its lanes are
        survivors-first, so the valid prefix holds the real flags and
        padding lanes' are discarded; None is the full-width program,
        indexed by worker id."""
        ll, acc, loss_sum, t_loss, t_acc, scr, sscr, em, diag = \
            self._unpack_host_metrics(
                vec, self.num_workers if lanes is None else lanes)

        def of_sel(v):
            return v[sel] if lanes is None else v[:len(sel)]

        self._apply_screen_feedback(t, sel, of_sel(scr), frows)
        if sscr is not None:
            for i in np.nonzero(sscr > 0.5)[0]:
                frows.append({"round": int(t), "worker": int(i),
                              "kind": "staleness",
                              "action": "screened_nonfinite_on_admission"})
        self.history.faults.extend(frows)
        self.history.append(
            round=t,
            test_acc=acc,
            test_loss=loss_sum,   # P1 summed-loss flavour
            train_loss=t_loss,
            train_acc=t_acc,
            local_loss=ll,
        )
        if self._holdout:
            self._append_client_rows(
                t, {k: of_sel(v) for k, v in em.items()}, sel)
        self._round_telemetry(t, frows, diag)
        self.round += 1

    def _round_dispatch(self, t: int, frac: float):
        """Round ``t``'s device dispatch, fully built: ``(fn_name,
        step_fn, args, kwargs, sel, sel_lanes, use_c, frows)``.  The
        ONE builder both the per-round ``run`` loop and ``lower_round``
        consume — which is what makes the program-fingerprint gate
        (``dopt.analysis.fingerprint``) pin the program the real loop
        actually dispatches, with no mirror to drift.  Advances the
        same stateful host draws (sampling RNG, ledger rows) the run
        loop would."""
        cfg, f = self.cfg, self.cfg.federated
        compact = self._use_compact(frac)
        fixed_c = compact and self.faults.active
        (sel, limits, cmask, frows, cap,
         admit) = self._round_participation(t, frac)
        if fixed_c:
            # Fixed-width compact fault lanes: survivors first, padding
            # ids after, validity as data — one compiled program for
            # every survivor count (no per-count retrace), identical
            # semantics to the old variable-width path up to float
            # summation order.
            sel_lanes, valid_np = self._fixed_width_sel(sel, frac)
        else:
            sel_lanes, valid_np = sel, None
        use_c = compact and sel_lanes.size > 0
        # Compact path: plan only the m sampled workers' rows — host
        # cost O(m), and the RNG is keyed by true worker id so the
        # plans are bit-identical to the full plan's rows.
        plan = make_batch_plan(
            self._plan_matrix_for_round(t), batch_size=f.local_bs,
            local_ep=f.local_ep, seed=cfg.seed, round_idx=t,
            impl=cfg.data.plan_impl,
            workers=sel_lanes if use_c else None)
        if use_c:
            idx = jnp.asarray(plan.idx)
            bweight = jnp.asarray(plan.weight)
            lim_dev = jnp.asarray(limits[sel_lanes])
        else:
            mask = np.zeros(self.num_workers, np.float32)
            mask[sel] = 1.0
            idx = jax.device_put(plan.idx, self._sharding)
            bweight = jax.device_put(plan.weight, self._sharding)
            lim_dev = jnp.asarray(limits)
        step_fn = self._compact_fn if use_c else self._round_fn
        gate = jnp.asarray(sel_lanes) if use_c else jnp.asarray(mask)
        step_kw = ({"cmask": jnp.asarray(
            cmask[sel_lanes] if use_c else cmask)}
            if self._has_corrupt else {})
        if fixed_c and use_c:
            step_kw["valid"] = jnp.asarray(valid_np)
        if self._has_stale:
            step_kw.update(
                load_mask=jnp.asarray(np.clip(mask + cap, 0.0, 1.0)),
                stale_p=self._stale_p,
                admit_w=jnp.asarray(admit),
                capture=jnp.asarray(cap))
        args = (self.theta, self.params, self.momentum,
                *self._dual_inputs(), gate, lim_dev, idx, bweight,
                self._train_x, self._train_y, *self._eval,
                self._train_eval_idx, self._train_eval_w, *self._val)
        return ("compact_fn" if use_c else "round_fn", step_fn, args,
                step_kw, sel, sel_lanes, use_c, frows)

    def lower_round(self, t: int | None = None,
                    frac: float | None = None):
        """Lower (without executing) round ``t``'s device step exactly
        as the per-round ``run`` loop would dispatch it — same
        ``_round_dispatch`` builder, so the two cannot diverge — and
        return ``(fn_name, jax.stages.Lowered)``.  The program-
        fingerprint hook; call it on a FRESHLY CONSTRUCTED trainer only
        (the participation draw advances the run loop's sampling
        RNG)."""
        if self._registry is not None:
            raise ValueError(
                "lower_round covers the worker==lane per-round paths; "
                "population mode dispatches the wave scan instead")
        f = self.cfg.federated
        frac = f.frac if frac is None else frac
        t = self.round if t is None else t
        fn_name, step_fn, args, step_kw, *_ = self._round_dispatch(
            t, frac)
        return fn_name, step_fn.lower(*args, **step_kw)

    def _unpack_host_metrics(self, vec: np.ndarray, lanes: int):
        """Inverse of the round step's ``pack_host_metrics``: one fetched
        f32 vector → (local_loss, test_acc, test_loss_sum, train_loss,
        train_acc, [lanes] screened flags, [lanes]
        screened-on-admission flags (staleness runs; else None), em dict
        of [lanes, E] arrays or {}, [6] diagnostics block (diagnostics
        runs; else None))."""
        ll, acc, loss_sum, t_loss, t_acc = (float(v) for v in vec[:5])
        scr = vec[5:5 + lanes]
        off = 5 + lanes
        sscr = None
        if self._has_stale:
            sscr = vec[off:off + lanes]
            off += lanes
        em: dict[str, np.ndarray] = {}
        if self._holdout:
            e = self.cfg.federated.local_ep
            n = lanes * e
            body = vec[off:]
            for i, k in enumerate(("train_loss", "train_acc", "val_acc",
                                   "val_loss")):
                em[k] = body[i * n:(i + 1) * n].reshape(lanes, e)
        diag = vec[-len(self._diag_keys):] if self._diag else None
        return ll, acc, loss_sum, t_loss, t_acc, scr, sscr, em, diag

    def _plan_matrix_for_round(self, t: int) -> np.ndarray:
        return self.faults.plan_matrix_for(t, self._train_matrix)

    def _append_client_rows(self, t: int, em: dict, workers) -> None:
        """Per-epoch per-client history rows (P1 Client.history schema,
        clients.py:50: {global_round, epoch, train_loss, train_acc,
        val_acc, val_loss} with val_loss in P1's summed-batch-loss
        flavour), one row per (sampled client, epoch)."""
        tl, ta = em["train_loss"], em["train_acc"]
        va, vl = em["val_acc"], em["val_loss"]
        for j, wid in enumerate(workers):
            for e in range(tl.shape[1]):
                self.client_history.append(
                    global_round=t, epoch=e, worker=int(wid),
                    train_loss=float(tl[j, e]), train_acc=float(ta[j, e]),
                    val_acc=float(va[j, e]), val_loss=float(vl[j, e]),
                )

    def _mirror_gauges(self) -> dict:
        """The staleness-buffer schedule, as of the round just
        replayed."""
        if not self._has_stale:
            return {}
        return {"stale_pending": float((self._stale_weight > 0).sum()),
                "stale_weight_total": float(self._stale_weight.sum())}

    def _consensus_operands(self):
        """The stacked lane params against theta; nothing in population
        mode (clients are stateless, the stacked lane params are not
        client state)."""
        if self._registry is not None:
            return None
        return self.params, self._theta_single()

    def _theta_single(self):
        """The single global model: row 0 of the carried [W, ...] slab
        under ``fused_update='on'`` (rows are bit-identical by the
        fused epilogue's contract), the replicated tree otherwise."""
        if self._fused_on:
            return jax.tree.map(lambda x: x[0], self.theta)
        return self.theta

    def _save(self, path) -> None:
        """(theta, stacked params, momentum, duals, round, history,
        sampling-RNG state).  Persisting the RNG state makes a resumed
        run draw the SAME client samples a continuous run would —
        without it, round t after resume replays round 0's sample."""
        from dopt.utils.checkpoint import save_checkpoint

        # Fused runs carry theta as the [W, ...] broadcast slab with
        # bit-identical rows — checkpoint row 0 (the single global
        # model), so fused and unfused checkpoints stay interchangeable
        # and W×|θ| never hits disk.
        theta_ck = (jax.tree.map(lambda x: x[0], self.theta)
                    if self._fused_on else self.theta)
        arrays = {"theta": theta_ck, "params": self.params}
        if self.cfg.federated.algorithm != "scaffold":
            # Scaffold momentum is per-round-local (always zeros between
            # rounds) — no point persisting a model-sized zero tree.
            arrays["momentum"] = self.momentum
        if self.duals is not None:
            arrays["duals"] = self.duals
        if self.c_global is not None:
            arrays["c_global"] = self.c_global
        if self._has_stale:
            # The staleness buffer + its host schedule are carried
            # state: without them a resumed run would mis-admit (or
            # lose) the in-flight late updates.
            arrays["stale_p"] = self._stale_p
        meta = {"round": self.round, "name": self.cfg.name,
                "algorithm": self.cfg.federated.algorithm,
                "history": self.history.rows,
                "client_history": self.client_history.rows,
                "fault_ledger": self.history.faults,
                "screen_streak": self._screen_streak.tolist(),
                "quarantine_until": self._quarantine_until.tolist(),
                "stale_admit_round": self._stale_admit_round.tolist(),
                "stale_weight": self._stale_weight.tolist(),
                "stale_origin": self._stale_origin.tolist(),
                "sample_rng_state": self._sample_rng.bit_generator.state}
        if self._registry is not None:
            # Registry state (participation counts, client-keyed streaks
            # and sentences, shard-assignment integrity check) — the
            # sampler itself is stateless, so this plus the round index
            # is everything a bit-exact mid-population resume needs.
            meta["population_registry"] = self._registry.state_dict()
        save_checkpoint(path, arrays=arrays, meta=meta,
                        write=self.checkpoint_writer)

    def restore(self, path) -> None:
        from dopt.utils.checkpoint import load_checkpoint

        arrays, meta = load_checkpoint(path)
        if meta.get("algorithm") != self.cfg.federated.algorithm:
            raise ValueError(
                f"checkpoint is for algorithm {meta.get('algorithm')!r}, "
                f"trainer runs {self.cfg.federated.algorithm!r}"
            )
        if self.duals is not None and "duals" not in arrays:
            raise ValueError(
                f"{self.cfg.federated.algorithm} trainer requires its "
                "worker-stacked companion state ('duals') in the checkpoint"
            )
        if self._fused_on:
            # Re-broadcast the checkpointed single theta onto the
            # worker-axis slab (rows are bit-identical by the fused
            # epilogue's contract, so this is resume-exact).
            self.theta = shard_worker_tree(
                jax.tree.map(
                    lambda x: np.ascontiguousarray(np.broadcast_to(
                        np.asarray(x)[None],
                        (self.num_workers,) + np.asarray(x).shape)),
                    arrays["theta"]),
                self.mesh)
        else:
            self.theta = jax.device_put(arrays["theta"], self._replicated)
        self.params = shard_worker_tree(arrays["params"], self.mesh)
        if "momentum" in arrays:
            self.momentum = shard_worker_tree(arrays["momentum"], self.mesh)
        if "duals" in arrays and self.duals is not None:
            self.duals = shard_worker_tree(arrays["duals"], self.mesh)
        if self.c_global is not None:
            if "c_global" not in arrays:
                raise ValueError(
                    "scaffold trainer requires the server control variate "
                    "('c_global') in the checkpoint")
            self.c_global = jax.device_put(arrays["c_global"],
                                           self._replicated)
        self.round = int(meta["round"])
        self.history.rows = list(meta.get("history", []))
        self.history.faults = list(meta.get("fault_ledger", []))
        self.client_history.rows = list(meta.get("client_history", []))
        w = self.num_workers
        self._screen_streak = np.asarray(
            meta.get("screen_streak", [0] * w), np.int64)
        self._quarantine_until = np.asarray(
            meta.get("quarantine_until", [0] * w), np.int64)
        if self._has_stale:
            if "stale_p" not in arrays:
                raise ValueError(
                    "staleness-aware trainer requires its late-update "
                    "buffer ('stale_p') in the checkpoint")
            self._stale_p = shard_worker_tree(arrays["stale_p"], self.mesh)
            self._stale_admit_round = np.asarray(
                meta.get("stale_admit_round", [0] * w), np.int64)
            self._stale_weight = np.asarray(
                meta.get("stale_weight", [0.0] * w), np.float64)
            self._stale_origin = np.asarray(
                meta.get("stale_origin", [0] * w), np.int64)
        if meta.get("sample_rng_state"):
            self._sample_rng.bit_generator.state = meta["sample_rng_state"]
        if self._registry is not None:
            from dopt.utils.checkpoint import meta_expect

            meta_expect(meta, what="population checkpoint",
                        algorithm=self.cfg.federated.algorithm)
            state = meta.get("population_registry")
            if state is None:
                raise ValueError(
                    "population-mode trainer requires its registry state "
                    "('population_registry') in the checkpoint — this "
                    "checkpoint is from a lane-engine run")
            self._registry.load_state(state)

    def evaluate_global(self) -> dict[str, float]:
        out = self._global_eval(self._theta_single(), *self._eval)
        return {k: float(v) for k, v in out.items()}
