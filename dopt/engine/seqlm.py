"""Sequence-parallel LM training — the long-context substrate as a
driveable component.

The reference has no attention and no sequence axis anywhere (SURVEY
§2.3: 2-layer CNNs on small images), so nothing here is owed for parity;
this engine exists so ``dopt.parallel.sequence`` (ring attention via
``lax.ppermute`` KV rotation; Ulysses via ``all_to_all`` head
resharding) is a trained component rather than a tested demo:
``python -m dopt.run --preset seqlm`` trains a decoder-only
``TransformerLM`` with the SEQUENCE axis sharded over the mesh.

Design (TPU-first):

* One 1-D mesh over the sequence axis (``make_seq_mesh``); token
  batches [B, L] are placed with L sharded, parameters replicated.
  Every position-wise op (embeddings, MLPs, LayerNorm, logits) runs on
  the local L/D shard under XLA SPMD with zero communication; only
  attention crosses shards, through the injected ``attn_fn``.
* The next-token shift ``logits[:, :-1] vs tokens[:, 1:]`` is written
  in the global view; XLA inserts the one-position halo exchange.
* Training data is a deterministic synthetic order-1 Markov token
  stream (seeded sparse transition table): a next-token model can cut
  loss far below the uniform baseline exactly when it learns the
  transitions, so loss-goes-down is a meaningful signal, offline.
* SGD + momentum (the framework's one optimizer) on the mean CE.

The trainer exposes the same surface as the other engines (``run``,
``history``, ``total_time``, ``save``/``restore``, ``timers``) so the
CLI, checkpoint, and plotting machinery drive it unchanged.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from dopt.config import ExperimentConfig
from dopt.models import build_model, count_params
from dopt.optim import SGDState, sgd_step
from dopt.parallel.sequence import (SEQ_AXIS, make_seq_mesh, ring_attention,
                                    ulysses_attention)
from dopt.utils.metrics import History
from dopt.utils.profiling import PhaseTimers


def markov_token_stream(vocab: int, n_tokens: int, *, seed: int,
                        branching: int = 4) -> np.ndarray:
    """Deterministic synthetic corpus: an order-1 Markov chain where
    each token has ``branching`` permitted successors (seeded uniform
    choice among them).  Perfect next-token prediction reaches
    ``log(branching)`` nats; an untrained model sits at ``log(vocab)``
    — the gap is what training closes."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 94_227]))
    table = np.stack([rng.choice(vocab, branching, replace=False)
                      for _ in range(vocab)])
    out = np.empty(n_tokens, np.int32)
    out[0] = rng.integers(vocab)
    draws = rng.integers(branching, size=n_tokens)
    for t in range(1, n_tokens):
        out[t] = table[out[t - 1], draws[t]]
    return out


class SeqLMTrainer:
    """Train ``TransformerLM`` with sequence-parallel attention."""

    def __init__(self, cfg: ExperimentConfig, *, mesh_devices: int | None = None):
        if cfg.seqlm is None:
            raise ValueError("cfg.seqlm must be set for SeqLMTrainer")
        s = cfg.seqlm
        if s.attn not in ("ring", "ulysses", "dense"):
            raise ValueError(
                f"unknown attn {s.attn!r}; one of ring|ulysses|dense")
        from dopt.engine.local import validate_optimizer

        validate_optimizer(cfg)
        self.cfg = cfg
        self.step = 0
        self.history = History(cfg.name)
        self.timers = PhaseTimers()

        n = mesh_devices if mesh_devices is not None else cfg.mesh_devices
        self.mesh = make_seq_mesh(n)
        d = self.mesh.size
        if s.attn == "dense" and d != 1:
            raise ValueError(
                "attn='dense' is the single-device path; use ring/ulysses "
                f"on a {d}-device mesh")
        if s.seq_len % d:
            raise ValueError(f"seq_len {s.seq_len} not divisible by the "
                             f"{d}-device mesh")
        if s.attn == "ulysses" and s.heads % d:
            raise ValueError(f"ulysses needs heads ({s.heads}) divisible by "
                             f"the mesh size ({d})")

        mesh = self.mesh
        if s.kv_chunk and s.attn != "ring":
            raise ValueError("kv_chunk only applies to attn='ring'")
        if s.attn == "ring":
            kv_chunk = s.kv_chunk or None
            attn_fn = lambda q, k, v: ring_attention(q, k, v, mesh,
                                                     causal=True,
                                                     kv_chunk=kv_chunk)
        elif s.attn == "ulysses":
            attn_fn = lambda q, k, v: ulysses_attention(q, k, v, mesh,
                                                        causal=True)
        else:
            attn_fn = None  # model falls back to dense causal attention

        self.model = build_model(
            "transformer", num_classes=s.vocab,
            dtype=cfg.model.compute_dtype,
        ).clone(dim=s.dim, depth=s.depth, heads=s.heads, max_len=s.seq_len)

        # Data: one resident token stream, sliced into [B, L] windows by
        # a deterministic per-step plan.
        # The stream stays HOST-side (numpy): batch assembly is pure
        # host slicing + one device_put per step; a device-resident
        # stream would force a device->host sync per window gather.
        self._stream = markov_token_stream(
            s.vocab, max(s.batch * s.seq_len * 8, 65_536), seed=cfg.seed)
        self._n_windows = len(self._stream) - s.seq_len - 1

        key = jax.random.key(cfg.seed)
        params = self.model.init(key, jnp.zeros((1, s.seq_len), jnp.int32),
                                 attn_fn=attn_fn)["params"]
        self.param_count = count_params(params)
        # Params replicated; token batches sequence-sharded.
        from jax.sharding import NamedSharding, PartitionSpec as P

        self._tok_sharding = NamedSharding(mesh, P(None, SEQ_AXIS))
        rep = NamedSharding(mesh, P())
        self.params = jax.device_put(params, rep)
        self.momentum = jax.device_put(
            jax.tree.map(np.zeros_like, jax.device_get(params)), rep)

        lr, mu = cfg.optim.lr, cfg.optim.momentum
        apply_fn = self.model.apply

        def loss_fn(p, tokens):
            logits = apply_fn({"params": p}, tokens, attn_fn=attn_fn)
            logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32))
            tgt = tokens[:, 1:]
            nll = -jnp.take_along_axis(logp, tgt[..., None], axis=-1)
            return nll.mean()

        def train_step(p, m, tokens):
            loss, g = jax.value_and_grad(loss_fn)(p, tokens)
            p, st = sgd_step(p, SGDState(m), g, lr=lr, momentum=mu)
            return p, st.momentum, loss

        self._train_step = jax.jit(train_step, donate_argnums=(0, 1))
        self._rng = np.random.default_rng(
            np.random.SeedSequence([cfg.seed, 777_001]))

    def _batch(self) -> jnp.ndarray:
        s = self.cfg.seqlm
        starts = self._rng.integers(self._n_windows, size=s.batch)
        toks = np.stack([self._stream[a:a + s.seq_len] for a in starts])
        return jax.device_put(toks, self._tok_sharding)

    def run(self, rounds: int | None = None, steps: int | None = None) -> History:
        """Train ``steps`` steps (``rounds`` is accepted as an alias so
        the CLI driver's --rounds flag works unchanged)."""
        s = self.cfg.seqlm
        n = steps if steps is not None else (rounds if rounds is not None
                                             else s.steps)
        t0 = time.time()  # dopt: allow-wallclock -- total_time wall meter, reporting only
        logged: list[tuple[int, jnp.ndarray]] = []
        for i in range(n):
            with self.timers.step(self.step):
                with self.timers.phase("host_batch_plan"):
                    toks = self._batch()
                self.params, self.momentum, loss = self.timers.measure(
                    "round_step", self._train_step, self.params,
                    self.momentum, toks)
            # i (run-relative) decides the always-log-final-step rule so
            # resumed/continued runs still close with a loss row.  Losses
            # stay ON DEVICE until the run ends — each device→host fetch
            # synchronises with the device, so the whole run's logged
            # losses travel as one stacked array.
            if self.step % s.log_every == 0 or i == n - 1:
                logged.append((self.step, loss))
            self.step += 1
        jax.block_until_ready(self.params)
        self.total_time = time.time() - t0  # dopt: allow-wallclock -- total_time wall meter, reporting only
        if logged:
            with self.timers.phase("round_fetch"):
                vals = np.asarray(jnp.stack([l for _, l in logged]))
            with self.timers.phase("round_record"):
                for (st, _), v in zip(logged, vals):
                    self.history.append(round=st, step=st, loss=float(v))
        return self.history

    @property
    def round(self) -> int:  # CLI-driver surface parity
        return self.step

    def save(self, path) -> None:
        from dopt.utils.checkpoint import save_checkpoint

        save_checkpoint(
            path,
            arrays={"params": self.params, "momentum": self.momentum},
            meta={"round": self.step, "name": self.cfg.name,
                  "algorithm": "seqlm", "history": self.history.rows,
                  "data_rng_state": self._rng.bit_generator.state},
        )

    def restore(self, path) -> None:
        from dopt.utils.checkpoint import load_checkpoint
        from jax.sharding import NamedSharding, PartitionSpec as P

        arrays, meta = load_checkpoint(path)
        if meta.get("algorithm") != "seqlm":
            raise ValueError(
                f"checkpoint is for {meta.get('algorithm')!r}, not seqlm")
        rep = NamedSharding(self.mesh, P())
        self.params = jax.device_put(arrays["params"], rep)
        self.momentum = jax.device_put(arrays["momentum"], rep)
        self.step = int(meta["round"])
        self.history.rows = list(meta.get("history", []))
        if meta.get("data_rng_state"):
            self._rng.bit_generator.state = meta["data_rng_state"]
