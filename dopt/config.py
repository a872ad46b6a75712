"""Typed, frozen experiment configuration.

The reference drives everything through a ``DotDict`` built from a Colab
form cell (``Decentralized Optimization/src/utils.py:14-27`` and the
notebook config cells); missing keys silently read as ``None`` and
several orchestrators mutate the shared args object
(``Distributed Optimization/src/simulators.py:171-180``).  ``dopt``
replaces that with frozen dataclasses while keeping the reference's
parameter *names* (num_users, frac, local_ep, local_bs, lr, momentum,
rho, topology, mode, shards, iid, seed) so every published experiment
config maps 1:1.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Mapping


@dataclass(frozen=True)
class DataConfig:
    """Dataset selection + partitioning (reference ``get_dataset`` args)."""

    dataset: str = "mnist"
    # mnist | fmnist | cifar10 | cifar100 | synthetic | a9a |
    # synthetic_tokens ([N, T] int32 id rows for a sequence model)
    iid: bool = True
    shards: int = 2          # non-IID shards per user (P2 sampling.py:11-28)
    num_users: int = 8
    data_dir: str | None = None   # directory with raw files; None -> auto/synthetic
    synthetic_train_size: int = 2048
    synthetic_test_size: int = 512
    # (no 'unequal' knob: the reference has no unequal split — P1's
    # hardcoded shard tables and P2's args.shards are both equal-size —
    # so the field would be a silent no-op; partitioners reject what
    # they cannot honour instead.)
    plan_impl: str = "numpy"  # "native" = C++ host runtime (dopt.native)
    # for per-round batch-plan generation; numpy remains the
    # torch-oracle-parity mode
    local_holdout: float = 0.0
    # Fraction of each worker's shard held out as LOCAL validation, the
    # reference's ``train_val_test`` split: ``val_size = max(int(L/10), 1)``
    # and training runs on the remaining samples only (P1 clients.py:25-28,
    # P2 clients.py:20-22).  0.1 reproduces the reference; 0.0 (default)
    # trains on the full shard (the idiomatic mode).  When enabled the
    # engines also emit per-epoch per-worker
    # {train_loss, train_acc, val_acc, val_loss} rows (clients.py:45-50)
    # into ``trainer.client_history``.
    holdout_mode: str = "deterministic"
    # deterministic — val = FIRST val_size indices of the worker's shard
    #                 (P1, clients.py:26-28).
    # random        — seeded random choice without replacement
    #                 (P2, clients.py:21-22).


# What each published ``config.json`` brings beyond the keys every decoder
# has, by ``model_type``: (keys it must give, keys it may give: stated by
# the source and held to one value or not read).  A key of one type alone
# is refused under the other.  Every type but ``smallthinker`` states its
# dense and expert widths and its experts a token as ``intermediate_size``,
# ``moe_intermediate_size`` and ``num_experts_per_tok`` (SmallThinker's
# are ``moe_ffn_hidden_size`` and ``moe_num_active_primary_experts``, and
# it has no dense layer).
_DECODER_KEYS = {
    "laguna": (("intermediate_size", "moe_intermediate_size",
                "num_experts_per_tok",
                "num_experts", "num_attention_heads_per_layer",
                "layer_types", "mlp_layer_types", "sliding_window",
                "rope_parameters", "shared_expert_intermediate_size",
                "moe_routed_scaling_factor", "gating"),
               ("moe_apply_router_weight_on_input",
                "partial_rotary_factor")),
    "KeyeVL2": (("intermediate_size", "moe_intermediate_size",
                 "num_experts_per_tok",
                 "num_experts", "sa_config", "norm_topk_prob",
                 "decoder_sparse_step", "rope_theta", "rope_scaling"),
                ("mlp_only_layers", "hidden_act", "max_window_layers",
                 "num_local_experts", "use_sliding_window",
                 "sliding_window")),
    "joyai_llm_flash": (("intermediate_size", "moe_intermediate_size",
                         "num_experts_per_tok",
                         "n_routed_experts", "n_shared_experts",
                         "routed_scaling_factor",
                         "norm_topk_prob", "scoring_func", "topk_method",
                         "n_group", "topk_group", "first_k_dense_replace",
                         "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                         "qk_rope_head_dim", "v_head_dim", "rope_theta",
                         "rope_interleave", "num_nextn_predict_layers"),
                        ("hidden_act", "rope_scaling", "qk_head_dim",
                         "moe_layer_freq", "ep_size")),
    "smallthinker": (("moe_num_primary_experts",
                      "moe_num_active_primary_experts", "moe_ffn_hidden_size",
                      "moe_primary_router_apply_softmax", "norm_topk_prob",
                      "rope_layout", "sliding_window_layout",
                      "sliding_window_size", "rope_theta"),
                     ("model_name", "rope_scaling")),
}
_SA_KEYS = {"indexer_head_dim", "indexer_num_heads", "indexer_num_kv_heads",
            "kv_chunk_size", "q_chunk_size", "topk"}


@dataclass(frozen=True)
class DecoderConfig:
    """A mixture-of-experts decoder (``dopt.models.decoder``) under the
    keys of its published ``config.json``; a key the file has and this
    class lacks is refused, not ignored, and so is a key of the other
    ``model_type``.  Four are known, and the type alone says which layer
    is built:

    ``laguna``: gated window/full attention with query heads, attention
    kind, rotary and MLP kind BY LAYER (lists over the PUBLISHED depth),
    sigmoid-routed experts scaled by ``moe_routed_scaling_factor``
    beside a shared one.

    ``KeyeVL2`` (the language model; no vision tower): every layer
    alike, per-head RMS norms on queries and keys, one rotary, a learned
    sparse attention (``sa_config``: a lightning indexer of
    ``indexer_num_heads`` heads of ``indexer_head_dim`` on ONE key head
    picks the ``topk`` keys a query attends, and an alignment term
    trains it), no output gate, softmax-routed experts with renormalised
    top-k weights and no shared one; layer i is sparse unless it is in
    ``mlp_only_layers`` or ``(i + 1) % decoder_sparse_step`` is not 0.

    ``joyai_llm_flash`` (DeepSeek-V3's keys, arXiv:2412.19437): every
    layer's attention is multi-head LATENT attention (queries through a
    ``q_lora_rank`` bottleneck, keys and values through a
    ``kv_lora_rank`` one, each with an RMS norm in the middle; query and
    key heads of ``qk_nope_head_dim + qk_rope_head_dim`` over value heads
    of ``v_head_dim``; ONE rotary key of ``qk_rope_head_dim`` shared by
    every head, interleaved pairs), the first ``first_k_dense_replace``
    layers dense and the rest sigmoid-routed experts chosen by score plus
    a correction bias (``topk_method: noaux_tc``) beside
    ``n_shared_experts`` shared ones, and ``num_nextn_predict_layers``
    (0 or 1) multi-token-prediction modules in the training loss.
    ``ep_size``, ``moe_layer_freq`` (1) and ``qk_head_dim`` (the sum) are
    stated and not read.

    ``smallthinker`` (the published file carries ``model_name`` and no
    ``model_type``; this is the repo's name for it): layer i's attention
    is banded by ``sliding_window_size`` where ``sliding_window_layout[i]``
    is 1 and full where it is 0, and turns q and k by rotary where
    ``rope_layout[i]`` is 1 and not at all (NoPE) where it is 0 (both
    lists over the PUBLISHED depth); no output gate; every layer routes to
    ``moe_num_active_primary_experts`` of ``moe_num_primary_experts``
    experts of ``moe_ffn_hidden_size`` by a softmax over the router's
    logits, renormalised over the chosen (``norm_topk_prob``), the router
    reading the layer's input BEFORE the attention; ReGLU experts
    (``relu`` gate) and no shared one.  ``intermediate_size``,
    ``num_experts_per_tok`` and ``moe_intermediate_size`` are the other
    types' keys (``expert_width``, ``experts_per_tok`` read either).

    A worker builds layers ``0 .. num_hidden_layers-1``, so a cut in
    depth changes one number.  ``experts_held`` / ``expert_offset`` say
    which of the ``num_experts`` published experts of a layer this
    worker holds (ids ``offset .. offset + held - 1``, a chip's share of
    an expert-parallel deployment; ``None`` = all).  The router keeps
    its published width and ``num_experts_per_tok``; what the absent
    experts would add is left out.  The vocabulary rows held are
    ``ModelConfig.num_classes``, the sequence length
    ``ModelConfig.input_shape[0]``."""

    hidden_size: int
    intermediate_size: int | None = field(default=None, kw_only=True)
    num_hidden_layers: int
    num_key_value_heads: int
    head_dim: int
    num_experts_per_tok: int | None = field(default=None, kw_only=True)
    moe_intermediate_size: int | None = field(default=None, kw_only=True)
    model_type: str = "laguna"
    num_experts: int | None = None        # n_routed_experts for joyai
    rms_norm_eps: float = 1e-6
    attention_bias: bool = False
    tie_word_embeddings: bool = False
    # model_type laguna:
    num_attention_heads_per_layer: tuple[int, ...] | None = None
    layer_types: tuple[str, ...] | None = None   # full_ | sliding_attention
    mlp_layer_types: tuple[str, ...] | None = None        # dense | sparse
    sliding_window: int | None = None
    rope_parameters: Mapping[str, Any] | None = None
    # {"full_attention": {...}, "sliding_attention": {...}}: rope_theta,
    # rope_type default | yarn (factor, original_max_position_embeddings,
    # beta_fast, beta_slow, attention_factor), partial_rotary_factor.
    shared_expert_intermediate_size: int | None = None
    moe_routed_scaling_factor: float | None = None
    gating: bool | None = None            # one sigmoid output gate a head
    moe_apply_router_weight_on_input: bool | None = None
    # model_type KeyeVL2:
    sa_config: Mapping[str, int] | None = None
    norm_topk_prob: bool | None = None
    decoder_sparse_step: int | None = None
    mlp_only_layers: tuple[int, ...] | None = None
    rope_theta: float | None = None
    rope_scaling: Mapping[str, Any] | None = None
    # {"rope_type": "default", "mrope_section": [...]}: on token rows the
    # three position components are equal and the sections collapse to
    # the plain rotary.
    hidden_act: str | None = None
    use_sliding_window: bool | None = None
    # model_type joyai_llm_flash:
    n_routed_experts: int | None = None
    n_shared_experts: int | None = None
    routed_scaling_factor: float | None = None
    scoring_func: str | None = None
    topk_method: str | None = None
    n_group: int | None = None
    topk_group: int | None = None
    first_k_dense_replace: int | None = None
    q_lora_rank: int | None = None
    kv_lora_rank: int | None = None
    qk_nope_head_dim: int | None = None
    qk_rope_head_dim: int | None = None
    v_head_dim: int | None = None
    rope_interleave: bool | None = None
    num_nextn_predict_layers: int | None = None
    qk_head_dim: int | None = None
    moe_layer_freq: int | None = None
    ep_size: int | None = None
    # model_type smallthinker:
    moe_num_primary_experts: int | None = None
    moe_num_active_primary_experts: int | None = None
    moe_ffn_hidden_size: int | None = None
    moe_primary_router_apply_softmax: bool | None = None
    rope_layout: tuple[int, ...] | None = None            # 1: rotary, 0: none
    sliding_window_layout: tuple[int, ...] | None = None  # 1: band, 0: full
    sliding_window_size: int | None = None
    model_name: str | None = None
    # Stated by the source and not read by the model (they describe the
    # whole checkpoint, or repeat another key):
    vocab_size: int | None = None
    num_attention_heads: int | None = None    # KeyeVL2 reads it
    max_position_embeddings: int | None = None
    partial_rotary_factor: float | None = None
    max_window_layers: int | None = None
    num_local_experts: int | None = None
    # The worker's share:
    experts_held: int | None = None
    expert_offset: int = 0

    def __post_init__(self) -> None:
        if self.model_type not in _DECODER_KEYS:
            raise ValueError(
                f"decoder.model_type {self.model_type!r}; one of "
                f"{' | '.join(_DECODER_KEYS)}")
        required, optional = _DECODER_KEYS[self.model_type]
        for name in required:
            if getattr(self, name) is None:
                raise ValueError(
                    f"decoder.{name} is required for model_type "
                    f"{self.model_type!r}")
        for kind, keys in _DECODER_KEYS.items():
            for name in (*keys[0], *keys[1]):
                if (getattr(self, name) is not None
                        and name not in required + optional):
                    raise ValueError(
                        f"decoder.{name} is a key of model_type {kind!r}, "
                        f"not of {self.model_type!r}")
        if self.attention_bias or self.tie_word_embeddings:
            raise ValueError(
                "the decoder has no biases and an untied head; "
                "attention_bias / tie_word_embeddings must be false")
        n = self.num_hidden_layers
        if self.model_type == "laguna":
            self._check_laguna(n)
        elif self.model_type == "KeyeVL2":
            self._check_keye()
        elif self.latent:
            self._check_joyai()
        else:
            self._check_smallthinker(n)
        if any(self.query_heads(i) % self.num_key_value_heads
               for i in range(n)):
            raise ValueError(
                "every layer's query heads must be a multiple of "
                f"num_key_value_heads={self.num_key_value_heads}")
        held = self.experts if self.experts_held is None else self.experts_held
        if not (0 < held and 0 <= self.expert_offset
                and self.expert_offset + held <= self.experts):
            raise ValueError(
                f"experts {self.expert_offset} .. {self.expert_offset + held - 1} "
                f"are not among the {self.experts} published")

    def _check_laguna(self, n: int) -> None:
        for name in ("num_attention_heads_per_layer", "layer_types",
                     "mlp_layer_types"):
            per_layer = tuple(getattr(self, name))
            object.__setattr__(self, name, per_layer)
            if len(per_layer) < n:
                raise ValueError(
                    f"decoder.{name} lists {len(per_layer)} layers, "
                    f"num_hidden_layers is {n}")
        if self.moe_apply_router_weight_on_input or not self.gating:
            raise ValueError(
                "a laguna layer has router weights on the experts' outputs "
                "and a gated attention output; "
                "moe_apply_router_weight_on_input must be false and gating "
                "true")

    def _check_keye(self) -> None:
        if self.num_attention_heads is None:
            raise ValueError("decoder.num_attention_heads is required for "
                             "model_type 'KeyeVL2'")
        object.__setattr__(self, "mlp_only_layers",
                           tuple(self.mlp_only_layers or ()))
        sa = dict(self.sa_config)
        if set(sa) != _SA_KEYS:
            raise ValueError(
                f"decoder.sa_config has the keys {sorted(_SA_KEYS)}, not "
                f"{sorted(sa)}")
        if sa["indexer_num_kv_heads"] != 1 or sa["topk"] < 1:
            raise ValueError(
                "the lightning indexer has ONE key head and keeps at least "
                "one key a query: sa_config.indexer_num_kv_heads must be 1 "
                "and topk positive")
        rope = dict(self.rope_scaling)
        if (rope.get("rope_type", "default") != "default"
                or rope.get("type", "default") != "default"):
            raise ValueError(
                "a KeyeVL2 layer has the plain rotary: "
                "rope_scaling.rope_type must be 'default'")
        if sum(rope.get("mrope_section", ())) not in (0, self.head_dim // 2):
            raise ValueError(
                "rope_scaling.mrope_section must cover half a head "
                f"({self.head_dim // 2} frequencies), not "
                f"{sum(rope['mrope_section'])}")
        if (not self.norm_topk_prob or self.decoder_sparse_step < 1
                or self.sliding_window is not None
                or self.use_sliding_window
                or (self.hidden_act or "silu") != "silu"
                or (self.num_local_experts or self.num_experts)
                != self.num_experts):
            raise ValueError(
                "a KeyeVL2 layer renormalises its top-k router weights, "
                "has no sliding window and a silu-gated MLP: norm_topk_prob "
                "must be true, decoder_sparse_step positive, sliding_window "
                "null, use_sliding_window false, hidden_act 'silu' and "
                "num_local_experts equal to num_experts")

    def _check_joyai(self) -> None:
        if self.num_attention_heads is None:
            raise ValueError("decoder.num_attention_heads is required for "
                             "model_type 'joyai_llm_flash'")
        if self.n_group != 1 or self.topk_group != 1:
            raise ValueError(
                "group-limited routing is not built: n_group and topk_group "
                "must be 1")
        if self.scoring_func != "sigmoid" or self.topk_method != "noaux_tc":
            raise ValueError(
                "the router scores by sigmoid and chooses by score plus its "
                "correction bias: scoring_func must be 'sigmoid' and "
                "topk_method 'noaux_tc'")
        if self.rope_scaling is not None:
            raise ValueError("the latent attention's rotary is unscaled: "
                             "rope_scaling must be null")
        if self.num_nextn_predict_layers not in (0, 1):
            raise ValueError("num_nextn_predict_layers must be 0 or 1")
        if self.moe_layer_freq not in (None, 1):
            raise ValueError(
                "every layer past first_k_dense_replace has experts: "
                "moe_layer_freq must be 1")
        if self.num_key_value_heads != self.num_attention_heads:
            raise ValueError(
                "latent attention has a key and a value head a query head: "
                "num_key_value_heads must equal num_attention_heads")
        rope, nope = self.qk_rope_head_dim, self.qk_nope_head_dim
        if self.head_dim != rope or self.qk_head_dim not in (None,
                                                             nope + rope):
            raise ValueError(
                f"head_dim is the rotary key's width ({rope}) and "
                f"qk_head_dim the query and key heads' ({nope} + {rope})")
        if (rope % 2 or not self.norm_topk_prob
                or (self.hidden_act or "silu") != "silu"):
            raise ValueError(
                "qk_rope_head_dim must be even, norm_topk_prob true and "
                "hidden_act 'silu'")

    def _check_smallthinker(self, n: int) -> None:
        if self.num_attention_heads is None:
            raise ValueError("decoder.num_attention_heads is required for "
                             "model_type 'smallthinker'")
        for name in ("rope_layout", "sliding_window_layout"):
            per_layer = tuple(getattr(self, name))
            object.__setattr__(self, name, per_layer)
            if len(per_layer) < n:
                raise ValueError(
                    f"decoder.{name} lists {len(per_layer)} layers, "
                    f"num_hidden_layers is {n}")
            if not set(per_layer) <= {0, 1}:
                raise ValueError(f"decoder.{name} holds 0 or 1 a layer")
        if not self.moe_primary_router_apply_softmax or not self.norm_topk_prob:
            raise ValueError(
                "a smallthinker router takes a softmax over its experts and "
                "renormalises the top-k: moe_primary_router_apply_softmax "
                "and norm_topk_prob must be true")
        if self.rope_scaling is not None:
            raise ValueError("a smallthinker layer's rotary is unscaled: "
                             "rope_scaling must be null")
        if self.sliding_window_size < 1:
            raise ValueError("sliding_window_size must be positive")

    # What layer i is, whichever model_type's keys say it.
    @property
    def experts(self) -> int:
        """The routed experts a layer publishes (the router's width)."""
        if self.smallthinker:
            return self.moe_num_primary_experts
        return self.n_routed_experts if self.latent else self.num_experts

    @property
    def experts_per_tok(self) -> int:
        """The experts the router sends a token to."""
        return (self.moe_num_active_primary_experts if self.smallthinker
                else self.num_experts_per_tok)

    @property
    def expert_width(self) -> int:
        """A routed expert's width."""
        return (self.moe_ffn_hidden_size if self.smallthinker
                else self.moe_intermediate_size)

    @property
    def smallthinker(self) -> bool:
        """The ``smallthinker`` layer: NoPE full and rotary banded
        attention by layer with no output gate, a softmax router that
        reads the layer's input BEFORE the attention, ReGLU experts and
        no shared one."""
        return self.model_type == "smallthinker"

    @property
    def softmax_router(self) -> bool:
        """Whether the router scores by a softmax over the published
        experts (else by a sigmoid each)."""
        return self.indexed or self.smallthinker

    @property
    def router_first(self) -> bool:
        """Whether the router reads the attention's normed input (before
        the attention) instead of the MLP's (after it)."""
        return self.smallthinker

    @property
    def output_gate(self) -> bool:
        """Whether the attention's output is gated, one sigmoid a head."""
        return self.model_type == "laguna"

    @property
    def expert_act(self) -> str:
        """The routed experts' gate activation: ``silu`` (SwiGLU) or
        ``relu`` (ReGLU)."""
        return "relu" if self.smallthinker else "silu"

    @property
    def latent(self) -> bool:
        """The ``joyai_llm_flash`` layer: multi-head latent attention, a
        bias-corrected sigmoid router beside shared experts, and a
        multi-token-prediction module in the loss."""
        return self.model_type == "joyai_llm_flash"

    @property
    def mtp(self) -> bool:
        """Whether the loss has a multi-token-prediction module."""
        return bool(self.latent and self.num_nextn_predict_layers)

    @property
    def routed_scale(self) -> float | None:
        """What the renormalised top-k weights are multiplied by (None:
        the ``KeyeVL2`` router does not scale them)."""
        return (self.routed_scaling_factor if self.latent
                else self.moe_routed_scaling_factor)

    @property
    def shared_width(self) -> int | None:
        """The shared expert's width, None where a layer has none."""
        if self.indexed or self.smallthinker:
            return None
        if self.latent:
            return self.n_shared_experts * self.moe_intermediate_size or None
        return self.shared_expert_intermediate_size

    @property
    def indexed(self) -> bool:
        """The ``KeyeVL2`` layer: an indexer picks the keys a query
        attends, per-head q/k norms, no output gate, a softmax router and
        no shared expert."""
        return self.model_type == "KeyeVL2"

    def query_heads(self, i: int) -> int:
        return (self.num_attention_heads_per_layer[i]
                if self.model_type == "laguna" else self.num_attention_heads)

    def window(self, i: int) -> int | None:
        """Positions back a query sees through a band; None = no band."""
        if self.smallthinker:
            return (self.sliding_window_size if self.sliding_window_layout[i]
                    else None)
        if (self.indexed or self.latent
                or self.layer_types[i] != "sliding_attention"):
            return None
        return self.sliding_window

    def rope(self, i: int) -> Mapping[str, Any] | None:
        """Layer i's ``rope_parameters`` entry (the latent attention's
        interleaves its pairs); None where the layer has no rotary (a
        smallthinker layer whose ``rope_layout`` entry is 0: NoPE)."""
        if self.smallthinker:
            return ({"rope_type": "default", "rope_theta": self.rope_theta}
                    if self.rope_layout[i] else None)
        if self.latent:
            return {"rope_type": "default", "rope_theta": self.rope_theta,
                    "interleave": bool(self.rope_interleave)}
        if self.indexed:
            return {"rope_type": "default", "rope_theta": self.rope_theta}
        return self.rope_parameters[self.layer_types[i]]

    def sparse_mlp(self, i: int) -> bool:
        """Whether layer i has experts (i = ``num_hidden_layers`` of the
        published depth is a multi-token-prediction module's)."""
        if self.smallthinker:
            return True
        if self.latent:
            return i >= self.first_k_dense_replace
        if self.indexed:
            return (i not in self.mlp_only_layers
                    and (i + 1) % self.decoder_sparse_step == 0)
        return self.mlp_layer_types[i] != "dense"


@dataclass(frozen=True)
class ModelConfig:
    """Model zoo selection (reference ``args.model`` string dispatch)."""

    model: str = "model1"    # model1 | model3 | mlp | resnet18 | logistic | decoder
    stage_sizes: tuple[int, ...] | None = None
    # resnet18 only: residual blocks per stage (None = the standard
    # (2, 2, 2, 2)).  Smaller values give shallow variants for tests
    # and the multichip dryrun, where a full-depth compile on one CPU
    # core would blow the time budget.
    faithful: bool = True
    # faithful=True reproduces the reference's Softmax-head +
    # CrossEntropyLoss double-softmax (models.py:22-27 + clients.py:11);
    # False uses the corrected logits head.
    num_classes: int = 10
    input_shape: tuple[int, ...] = (28, 28, 1)   # NHWC (TPU-native layout)
    param_dtype: str = "float32"
    # Storage dtype of the worker-stacked training state (params,
    # momentum, duals/controls): "bfloat16" halves HBM for the [W, ...]
    # stacked tree and the bytes every consensus/aggregation collective
    # moves, at a numerics cost (the update itself then rounds to bf16
    # each step).  float32 is the oracle-parity mode.
    compute_dtype: str = "float32"   # "bfloat16" for the fast path
    stacked_impl: str = "auto"
    # How the engines execute the per-worker forward over the [W, ...]
    # stacked state: "auto" uses the grouped-conv stacked program where
    # one exists (model1/model3 — dopt.models.make_stacked_apply; ~3×
    # faster than the vmap on TPU, identical math up to float
    # reassociation inside the conv), "vmap" forces the vmapped
    # per-worker path (the bit-level oracle-parity mode).
    decoder: DecoderConfig | None = None
    # model="decoder" (or, as the first one was named, "laguna") only:
    # the decoder's published configuration, whose ``model_type`` says
    # which layer is built (a mapping is taken as
    # DecoderConfig(**mapping)).

    def __post_init__(self) -> None:
        if isinstance(self.decoder, Mapping):
            object.__setattr__(self, "decoder",
                               DecoderConfig(**self.decoder))


@dataclass(frozen=True)
class OptimizerConfig:
    """Local SGD settings (reference ``clients.py`` optimizer construction)."""

    optimizer: str = "sgd"
    # Only 'sgd' exists (the reference's single optimizer,
    # clients.py:14); anything else is rejected loudly at trainer
    # construction rather than silently running SGD.
    lr: float = 0.01
    momentum: float = 0.5
    weight_decay: float = 0.0
    # ℓ2 coefficient added to the local loss (λ‖θ‖²/2, as an explicit
    # loss term rather than torch-style decoupled decay so FedProx/ADMM
    # gradient edits compose with it identically on both backends).
    rho: float = 0.1   # FedProx proximal weight / FedADMM penalty
    clip_norm: float = 0.0
    # Per-worker global-norm gradient clip applied to the final gradient
    # (after any FedProx/ADMM/SCAFFOLD edit), 0 = off.  Off by default:
    # the reference has no clipping and the faithful oracle contract
    # pins its exact update.  The corrected-head (faithful=False) CNNs
    # need it in bf16 — raw-logit CE on the un-normalised reference
    # architecture sits at the edge of stability at the reference lr,
    # and bf16 gradient rounding tips runs across it (measured
    # run-to-run final-acc scatter 0.3–0.97; clip 1.0 removes it —
    # results/bench_idiomatic.json).
    fused_update: bool = False  # pallas single-pass momentum-SGD update
    # (dopt.ops.fused_update); numerics identical to the jnp path


@dataclass(frozen=True)
class FederatedConfig:
    """Server-coordinated path (reference P1 ``servers.py``)."""

    algorithm: str = "fedavg"   # fedavg | fedprox | fedadmm | scaffold
    frac: float = 0.1           # fraction of users sampled per round
    rounds: int = 20
    local_ep: int = 10
    local_bs: int = 50
    compact: bool | None = None
    # Compact-sampling fast path: gather the m sampled workers' state
    # into [m, ...] lanes, train only those, scatter back — instead of
    # training all N lanes and mask-discarding (the faithful wart).
    # None = auto (on for a single-device mesh when frac < 1); numerics
    # match the full-width path up to float summation order.
    block_rounds: int = 1
    # >1 fuses that many rounds into one lax.scan jit dispatch (same
    # math, same per-round eval cadence) — the dispatch-overhead killer
    # for small models; mirrors GossipConfig.block_rounds.
    comm_dtype: str | None = None
    # Wire-only compression of the aggregation reduce (full-width path
    # on a sharded mesh): per-device partial sums cross ICI/DCN at this
    # dtype (e.g. "bfloat16"); local math stays full precision.
    # Mirrors GossipConfig.comm_dtype.
    staleness_max: int = 0
    # Staleness-aware aggregation (0 = off, the hard-drop reference
    # semantics).  When > 0, a deadline-missed straggler
    # (``FaultConfig.straggler_policy="drop"``) or a delay-faulted
    # uplink (``FaultConfig.msg_delay``) is no longer discarded: the
    # client finishes its full local work, its update is buffered, and
    # it is admitted into the aggregate of round t+d (d <=
    # staleness_max; later arrivals are dropped) with weight
    # ``staleness_decay**d`` — so late work still moves theta, just
    # with discounted trust.  Admitted updates pass the same non-finite
    # screen as immediate ones and respect quarantine, composing with
    # the Byzantine path.  Forces full-width per-round execution;
    # fedavg/fedprox only (SCAFFOLD/ADMM companion state has no
    # late-admission semantics).
    staleness_decay: float = 0.5
    # Per-round decay of a buffered update's aggregation weight: an
    # update admitted d rounds late enters the weighted average with
    # weight decay**d (1.0 = late counts like fresh, small = distrust
    # stale work).
    update_sharding: str = "off"
    # "off" | "scatter".  "scatter" runs the aggregation/weight-update
    # hot path sharded (Xu et al., arXiv:2004.13336): the parameter
    # tree is flattened into size-bounded buckets
    # (``update_bucket_mb``), each device reduce-scatters its masked
    # partial sums so it owns only a 1/D shard of the flat sum, the
    # aggregation update (the divide) runs on that shard, and one
    # all-gather re-forms the replicated theta — instead of every
    # device redundantly computing the full |θ| average.  Per-bucket
    # collectives overlap with compute under the XLA latency-hiding
    # scheduler (libtpu's default).
    # "off" compiles the exact pre-change program (bit-identical).
    # Requires aggregator='mean', no comm_dtype/staleness/compact, and
    # a flat 1-D mesh; numerics match the dense path to f32 summation
    # order (allclose, not bit-equal), and scatter-vs-scatter runs are
    # bit-reproducible and resume-exact.
    update_bucket_mb: float = 4.0
    # Scatter-mode bucket size bound (per-worker payload MB per
    # bucket): small enough that several collectives are in flight for
    # the scheduler to overlap, large enough to amortise collective
    # launch overhead.
    fused_update: str = "off"
    # "off" | "on".  "on" restructures the full-width round carry so
    # the aggregation epilogue (masked average of the survivors' new
    # params) runs as ONE fused Pallas pass over the flat-bucket
    # UpdateShardSpec layout (``dopt.ops.fused_mix_update``): the carry
    # holds theta BROADCAST over the worker axis, each round contracts
    # the masked per-lane displacements (p_i − theta) with the
    # mean-weight matrix and adds theta back in the same HBM pass —
    # equal to the jnp masked_average path to f32 summation order
    # (allclose, not bit-equal), and fused-vs-fused runs are
    # bit-reproducible, blocked-exact and resume-exact.  "off" (the
    # default) compiles the exact pre-change programs (fingerprint-
    # gated, bit-identical).  fedavg/fedprox full-width mean only:
    # rejected (loudly) with scaffold/fedadmm, staleness-aware
    # aggregation, robust aggregators, clip_radius, corrupt faults,
    # compact gather, update_sharding='scatter', comm_dtype,
    # population mode, and multi-device meshes.
    prefetch: str = "off"
    # "off" | "on".  "on" overlaps the host pipeline with device
    # compute on the blocked/chaos-blocked/population run loops: block
    # b+1's batch plans are built and staged to device
    # (``dopt.data.prefetch.PrefetchStager``) while block b runs —
    # dispatch → stage-next → fetch instead of build → dispatch →
    # fetch.  Stateful host draws (the client-sampling stream) stay on
    # the main thread in block order and the post-fetch ledger replay
    # consumes the drawn inputs, so prefetch-on runs are BIT-IDENTICAL
    # to prefetch-off (History, fault ledger, telemetry canonical
    # stream), and staging never crosses a checkpoint boundary so
    # kill-and-resume stays exact.  "off" (the default — the
    # oracle-parity mode) runs the exact pre-change host loop.
    # Rejected for population mode with client-keyed quarantine armed
    # (next round's eligibility depends on this round's screen
    # feedback, which only exists after the fetch).
    diagnostics: str = "off"
    # "off" | "on".  "on" computes per-round convergence diagnostics
    # INSIDE the compiled round (global update/gradient/parameter L2
    # norms, per-lane train-loss mean + max-min spread, and the fleet
    # lane-dispersion mean_i ||p_i - theta||), threads them through the
    # blocked lax.scan as extra packed outputs, and emits them as
    # deterministic ``gauge`` telemetry at the post-fetch boundary —
    # per-round, fused-blocked, prefetched and killed-and-resumed runs
    # produce canonically identical diagnostic streams (dopt.obs).
    # Also arms the non-deterministic device-resource channel
    # (``resource`` HBM samples per block, ``compile`` retrace events)
    # when telemetry is attached.  "off" (default) compiles the exact
    # pre-change programs and runs the exact pre-change host loop.
    # Rejected for population mode (stateless wave clients carry no
    # lane momentum/params to diagnose).


@dataclass(frozen=True)
class GossipConfig:
    """Serverless gossip/consensus path (reference P2 ``simulators.py``)."""

    algorithm: str = "dsgd"     # dsgd | nocons | centralized | fedlcon | gossip | choco
    topology: str = "circle"    # circle | star | complete | dynamic | random
    #                           # | torus | hierarchical | one_peer_exp
    # 'one_peer_exp' is the one-peer time-varying exponential schedule
    # (arXiv:2410.11998): round t mixes every worker with exactly ONE
    # peer at shift 2^(t mod log2 n), W_t = (I + P_{2^t})/2 with exact
    # dyadic weights (power-of-2 worker counts only).  The schedule is
    # stateless per round (pure function of t, like FaultPlan draws) so
    # it is bit-reproducible, blocked-exact and resume-exact, and its
    # shift union {0, 1, 2, ..., n/2} rides the sharded circulant
    # ppermute path (comm_impl='shift'/'auto') — O(lanes·|θ|) bytes per
    # round instead of the dense all-gather.
    mode: str = "stochastic"    # stochastic | double_stochastic | metropolis | uniform | ones
    rounds: int = 10
    local_ep: int = 4
    local_bs: int = 128
    eps: int = 1                # consensus sweeps per round (FedLCon)
    eval_mode: str = "full"     # full | sharded
    # How the per-round fleet eval reads the test set.  'full' is the
    # reference's semantics (EVERY client evaluates the ENTIRE test
    # split, P2 clients.py:71-86) — W·|test| sample-forwards per eval,
    # which on baseline5 costs more device time than the training round
    # itself (3.1 of 5.5 s/round measured).  'sharded' gives each
    # worker a round-robin 1/W shard: the fleet-MEAN metric is an
    # unbiased estimate from |test| total forwards, per-worker rows are
    # ~W× noisier.  Throughput trims use 'sharded'; parity runs keep
    # 'full'.
    mixing: str = "sync"        # consensus timing: sync | async
    # 'sync' (default) is the bulk-synchronous mix: round t's consensus
    # reads round t's neighbor state — the exact pre-change program.
    # 'async' is staleness-1 overlapped gossip (the communication/
    # compute overlap of arXiv:2410.11998 / D-PSGD practice): round t
    # mixes x_i <- W_ii·x_i(t) + Σ_{j≠i} W_ij·x_j(t-1), consuming the
    # PREVIOUS round's neighbor state via a double-buffered carry in
    # the blocked lax.scan — round r's neighbor communication fully
    # overlaps round r+1's local compute, and a late peer's stale
    # shard never stalls the round.  Round 0 mixes the shared init, so
    # async round 0 ≡ sync round 0.  The prev buffer is scan carry +
    # a checkpoint array ("async_prev"), keeping async runs
    # bit-reproducible, blocked-exact and resume-exact; crash/churn
    # repair applies to the FULL matrix before the diag/off-diag
    # split, so a departed worker's lanes degrade to self-weight
    # (identity row → pure local step) instead of blocking the mix.
    # dsgd-only; rejected with the robust layer, link faults/push_sum,
    # eps sweeps, update_sharding='scatter' and population mode.
    comm_impl: str = "auto"     # consensus collective: auto | dense | shift
    # 'dense'  — all_gather + contraction with the [n, n] mixing matrix
    #            (right for complete/random/arbitrary graphs).
    # 'shift'  — lax.ppermute over ICI: the [n, n] circulant decomposes
    #            into device-level ring rotations + a static lane slice
    #            (workers fold onto devices in n/D lanes), moving
    #            O(rotations·lanes·|θ|) bytes/round instead of the dense
    #            O(n·|θ|).  Requires a flat 1-D mesh and a topology
    #            whose schedule decomposes into circulant shifts.
    # 'auto'   — shift when those conditions hold and the ppermute bytes
    #            beat the all_gather with a 2× margin; dense otherwise.
    # Determinism note: runs are bit-reproducible for a fixed config AND
    # mesh, but 'auto' picks per mesh shape, and the two paths can
    # differ in the last float bit for non-dyadic weights (gemm FMA vs
    # mul+add); pin 'dense' or 'shift' for cross-hardware bit-replay.
    block_rounds: int = 1       # rounds fused into ONE jit (lax.scan) per
    # dispatch; >1 removes per-round host sync + dispatch overhead (the
    # fast path for throughput; eval happens at block boundaries only)
    faithful_bugs: bool = False
    # faithful_bugs=True replicates documented reference bugs (FedLCon's
    # stale new_weights accumulation, simulators.py:189-196) for oracle
    # comparison; the idiomatic path fixes them.
    self_weight: bool = False   # reference mixing has zero diagonal (SURVEY §6.2)
    hier_groups: int = 2        # topology='hierarchical': group count
    hier_period: int = 4        # ... global (cross-DCN) mix every N rounds
    choco_gamma: float = 1.0    # CHOCO-SGD consensus step size γ
    # CHOCO theory wants γ scaled DOWN with the compressor's contraction
    # factor δ (γ ≈ δ·spectral-gap terms); γ=1 is only safe because
    # compression_ratio defaults to 1 (identity → exact D-SGD).  With a
    # real compressor (ratio < 1 or qsgd) keep γ well below 1 — e.g.
    # γ≈0.1·ratio — or the consensus step can diverge; the trainer warns
    # on the risky combination.
    compression: str = "topk"   # CHOCO compressor: topk | randk | qsgd | none
    compression_ratio: float = 1.0
    # topk/randk: fraction of entries communicated (ratio=1 = identity;
    # with γ=1 that reduces exactly to D-SGD — tested; randk keeps a
    # FIXED k = ceil(ratio·n) index set per round, so wire size is
    # constant).  qsgd: ratio sets the quantization level count
    # (ratio=1 → 256 levels, not the identity — use compression='none'
    # for the exact reduction), unless qsgd_levels overrides it.
    # algorithm='choco' (Koloskova et al. 2019): workers gossip a
    # COMPRESSED difference Q(x_i − x̂_i) with error feedback, then take
    # the consensus step x_i += γ·((W x̂)_i − x̂_i).
    qsgd_levels: int = 0
    # Explicit QSGD level count (e.g. 16 = 4-bit range); 0 derives the
    # count from compression_ratio (ratio·256).  Separate knob so the
    # quantizer is not configured through the sparsifiers' fraction
    # semantics; only valid with compression='qsgd'.
    comm_dtype: str | None = None
    # Communication compression for the consensus collective: e.g.
    # "bfloat16" narrows model shards BEFORE the cross-worker
    # contraction/ppermute, halving ICI/DCN bytes per gossip round;
    # params and local compute stay at their own dtype.  None =
    # communicate at the compute dtype.
    #
    # Determinism note: with comm_dtype set, the two comm_impl paths are
    # NOT bit-identical — the dense path narrows every gathered lane,
    # while the shift path keeps locally-sourced lanes (shift 0 and the
    # q==0 parts of shifts that straddle a device's lane fold) exact.
    # Compressed-mode results therefore depend on comm_impl AND on the
    # mesh shape / lane fold (workers-per-device).  Exact-dtype runs
    # (comm_dtype=None) are bit-identical across both paths and any
    # fold — that equality is what the test suite pins.
    correction: str = "none"
    # Gossip bias correction under asymmetric message loss: "none" runs
    # the plain consensus (receiver rows renormalised after drops — the
    # effective matrix is then no longer doubly stochastic and the fleet
    # converges to a BIASED weighted average), "push_sum" runs push-sum /
    # ratio consensus (Kempe et al.; Stochastic Gradient Push, Assran et
    # al. 2019): every worker carries a scalar mass weight alongside its
    # parameters, both travel through the SAME column-stochastic
    # (mass-conserving) effective matrix, and the de-biased estimate is
    # params/mass — exact-mean consensus under arbitrary drop/delay
    # traces.  "push_sum" forces the dense comm path and per-round
    # execution; with no link faults and a doubly-stochastic schedule
    # the mass stays exactly 1.0 (divide/multiply by 1.0 is exact).
    update_sharding: str = "off"
    # "off" | "scatter".  "scatter" runs the consensus mix on a 1/D
    # shard of the FLATTENED parameter tree (arXiv:2004.13336 applied
    # to gossip): the tree is bucketed into size-bounded [W, Fb] slabs
    # (``update_bucket_mb``), the dense mix becomes per-device partial
    # contraction + ``psum_scatter`` (no device ever materialises the
    # [n, |θ|] gathered fleet state), the ppermute/shift schedule runs
    # as a sharded circulant contraction over the same flat buckets,
    # and the per-bucket collectives overlap with compute under the
    # XLA latency-hiding scheduler.  "off" compiles the exact
    # pre-change program (bit-identical).  Eligible for dsgd/fedlcon/
    # gossip with crash/straggler/partition/churn faults and blocked
    # execution; rejected (loudly) with the robust layer, link faults/
    # push-sum, choco, comm_dtype, and hybrid meshes.  Numerics: f32
    # trees agree with the dense path to summation order (the
    # allclose-pinned contract); bf16 trees additionally keep the
    # mixing matrix + accumulation in f32 where the dense path
    # contracts at bf16 — strictly more precise, but a larger delta vs
    # dense.  Scatter-vs-scatter is bit-reproducible and resume-exact.
    update_bucket_mb: float = 4.0
    # Scatter-mode bucket size bound (per-worker payload MB per
    # bucket); see FederatedConfig.update_bucket_mb.
    fused_update: str = "off"
    # "off" | "on".  "on" restructures the gossip scan carry into
    # (post-mix params, displacement buffer) so the round's consensus
    # epilogue runs as ONE fused Pallas pass over the flat-bucket
    # UpdateShardSpec layout (``dopt.ops.fused_mix_update``): the mix
    # contracts the PREVIOUS round's pre-update params with W and
    # applies the buffered local displacement in the same HBM pass
    # (q_t = W·q_{t-1} − fbuf, fbuf = q_{t-1} − p'_{t-1}).  This is
    # the D-PSGD update ordering (Lian et al., arXiv:1705.09056: the
    # local displacement is applied UNMIXED after the contraction) — a
    # documented variant of the default mix-then-step trajectory, NOT
    # bit-equal to it; the fused trajectory is pinned f32-allclose to
    # its own jnp reference (``dopt.ops.mix_sgd_reference``) and
    # fused-vs-fused runs are bit-reproducible, blocked-exact and
    # resume-exact (the displacement buffer rides the scan carry and
    # the checkpoint as "fused_buf").  "off" (the default) compiles
    # the exact pre-change programs (fingerprint-gated,
    # bit-identical).  dsgd/gossip dense single-sweep consensus only:
    # rejected (loudly) with the robust layer, link faults/push-sum,
    # mixing='async', choco, fedlcon eps sweeps, nocons/centralized,
    # update_sharding='scatter', comm_dtype, comm_impl='shift',
    # population mode, and multi-device meshes.
    prefetch: str = "off"
    # "off" | "on".  "on" overlaps the host pipeline with device
    # compute on the blocked run loops (clean, link-mode and
    # fused-quarantine): block b+1's batch plans + stacked
    # fault/link/corrupt inputs are built and staged to device while
    # block b runs (``dopt.data.prefetch.PrefetchStager``).  Stateful
    # draws (the 'gossip' matching-matrix stream) stay on the main
    # thread in block order and the post-fetch ledger replay reuses
    # the drawn inputs, so prefetch-on runs are BIT-IDENTICAL to
    # prefetch-off (History, fault ledger, telemetry canonical
    # stream); staging never crosses a checkpoint boundary, keeping
    # kill-and-resume exact.  "off" (the default — the oracle-parity
    # mode) runs the exact pre-change host loop.  Rejected in
    # population mode (the gossip cohort binding mutates the registry
    # and appends its ledger row at plan time — the federated engine
    # is the prefetch-eligible population path).
    diagnostics: str = "off"
    # "off" | "on".  "on" computes per-round convergence diagnostics
    # INSIDE the compiled round (global update/gradient/parameter L2
    # norms, per-lane train-loss mean + max-min spread, and the TRUE
    # per-round consensus distance mean_i ||p_i - p_bar||), threads
    # them through the blocked lax.scan as extra packed outputs, and
    # emits them as deterministic ``gauge`` telemetry at the post-fetch
    # boundary — per-round, fused-blocked, prefetched and
    # killed-and-resumed runs produce canonically identical diagnostic
    # streams (dopt.obs).  Also arms the non-deterministic
    # device-resource channel (``resource`` HBM samples per block,
    # ``compile`` retrace events) when telemetry is attached.  "off"
    # (default) compiles the exact pre-change programs and runs the
    # exact pre-change host loop.
    dropout: float = 0.0
    # DEPRECATED back-compat alias for FaultConfig(crash=p) — warns at
    # trainer construction and produces the identical fault trace
    # (dopt.faults.FaultPlan synthesizes the config); set
    # ExperimentConfig.faults instead.  Scheduled for REMOVAL in release
    # 0.2.0.  Per-round probability each worker is down: down workers
    # skip consensus AND local training, the mixing matrix is repaired
    # (dopt.topology.repair_for_dropout — the degenerate all-links-down
    # case of the per-edge link-fault model, see FaultConfig.msg_drop)
    # and they rejoin with stale params.


@dataclass(frozen=True)
class FaultConfig:
    """Deterministic fault injection (``dopt.faults.FaultPlan``).

    The reference assumes every simulated worker is alive and instant
    (SURVEY §5); real decentralized systems treat crashes, stragglers
    and partitions as the steady state.  All draws are keyed by
    (seed, round) — stateless — so the same config replays the same
    fault trace, per-round and blocked execution inject identical
    faults, and a killed-and-resumed run sees exactly the faults a
    continuous run would.  Every injected fault lands in the run's
    fault ledger (``History.faults``)."""

    crash: float = 0.0
    # Per-round per-worker crash probability.  A crashed worker is down
    # for the round: it skips consensus and local training (gossip) or
    # contributes nothing to the server aggregate (federated) and
    # rejoins next round with stale-but-valid state.
    straggle: float = 0.0
    # Per-round per-worker straggler probability (crashes win ties).
    straggle_frac: float = 0.5
    # Fraction of its local work a straggler finishes before the round
    # deadline: epochs under the holdout's epoch loop, SGD steps on the
    # flat path (ceil(frac * total), so frac > 0 always does some work).
    straggler_policy: str = "partial"
    # Federated only: 'partial' aggregates the straggler's truncated
    # update; 'drop' removes it from the round (FedAvg-paper server
    # deadline) — combine with over_select so the aggregate still
    # averages ~m clients.  Gossip has no server deadline and always
    # applies 'partial'.
    over_select: float = 0.0
    # Federated: sample ceil(m·(1+over_select)) clients, keep the first
    # m survivors after crashes/deadline drops (surplus is released and
    # ledgered) — the FedAvg-paper over-selection pattern.
    partition: float = 0.0
    # Per-round probability a network partition STARTS; while active,
    # the fleet is split into partition_groups random groups.  Gossip:
    # cross-group mixing edges are cut (matrix repaired as data,
    # ``repair_for_partition``).  Federated: only group 0 can reach the
    # server; other groups are unreachable for the span.
    partition_span: int = 2     # rounds a partition lasts once started
    partition_groups: int = 2   # number of sides of the cut
    corrupt: float = 0.0
    # Per-round per-worker probability the worker LIES: its contributed
    # update (federated) / the state it broadcasts to neighbors (gossip)
    # is replaced by a corrupted value before aggregation — the
    # Byzantine threat model, vs. crash's fail-stop model.  Crashes win
    # ties (a down worker sends nothing).  Injection happens INSIDE the
    # jitted round functions (``dopt.faults.corrupt_update``) from the
    # same stateless per-round streams, so corrupted runs stay
    # bit-reproducible, blocked-execution-exact and resume-exact.
    corrupt_mode: str = "nan"
    # What the lie looks like: 'nan' | 'inf' (non-finite poison),
    # 'scale' (norm blow-up by corrupt_scale), 'signflip' (update
    # negated through the reference point), 'stale' (replay of the
    # worker's previous update; federated engine only — gossip carries
    # no per-worker previous-send state).
    corrupt_scale: float = 100.0   # blow-up factor for mode='scale'
    corrupt_max: int = 0
    # Cap on corrupted workers per round (0 = no cap).  The cap keeps
    # the LOWEST-INDEXED workers among the round's draws, so
    # ``corrupt=1.0, corrupt_max=f`` pins workers 0..f-1 as PERSISTENT
    # adversaries — the classic fixed-f Byzantine setting robust
    # aggregators state their breakdown points against.
    msg_drop: float = 0.0
    # Per-round per-DIRECTED-EDGE message-loss probability (the lossy-
    # link model).  Each direction of each link draws independently, so
    # loss is asymmetric in general — which is exactly what makes the
    # row-renormalised effective mixing matrix non-doubly-stochastic
    # and plain gossip converge to a biased average (the push-sum
    # correction, ``GossipConfig.correction="push_sum"``, recovers the
    # true mean).  Gossip: the edge is cut for the round and the
    # surviving weights repaired as data.  Federated: the probability a
    # sampled client's UPLINK to the server loses the round's update
    # (the client keeps its local state; the server sees a failure).
    msg_delay: float = 0.0
    # Per-round per-directed-edge message-DELAY probability.  A delayed
    # gossip edge delivers the sender's state d rounds late (d drawn
    # uniformly in 1..msg_delay_max), so the receiver mixes against a
    # stale value — the bounded-staleness asynchronous-gossip model.
    # The staleness buffer is engine state, carried through blocked
    # execution and checkpoints.  Federated: a sampled client's uplink
    # update arrives d rounds late; with
    # ``FederatedConfig.staleness_max`` > 0 it is buffered and admitted
    # with decay weighting, otherwise it is lost like a drop.
    msg_delay_max: int = 2
    # Maximum delay D in rounds (the staleness bound; buffer depth is
    # compiled from it, so keep it small).
    churn: float = 0.0
    # Per-round per-worker probability an elastic-membership LEAVE event
    # starts: the worker departs the fleet for ``churn_span`` rounds and
    # then rejoins (the join event) with its stale state.  While away
    # the mixing matrix is repaired around it (identity row — same
    # healing as a crash) / it is excluded from federated sampling, and
    # its data shard is deterministically reassigned to the next alive
    # worker (``dopt.data.partition.reassign_shards``) so the departed
    # data keeps being trained on.  Draws are stateless per round like
    # every other fault kind.
    churn_span: int = 4         # rounds a departed worker stays away
    seed: int | None = None     # fault-stream seed; None = experiment seed


@dataclass(frozen=True)
class RobustConfig:
    """Byzantine-robust aggregation & quarantine (``dopt.robust``).

    The defense side of the threat model: ``FaultConfig.corrupt``
    injects lies, this config decides what the aggregation layer does
    about them.  ``None`` (or all defaults) keeps the exact masked-mean
    programs — clean runs stay bit-identical."""

    aggregator: str = "mean"
    # Federated server aggregation over the round's surviving updates:
    # 'mean' (the reference masked average, breakdown point 0),
    # 'trimmed_mean' (coordinate-wise, tolerates < trim_frac·n liars),
    # 'median' (coordinate-wise, breakdown 1/2), 'krum' / 'multi_krum'
    # (distance-based selection, tolerates f with n > 2f + 2).
    # All are jittable pure functions of (stacked updates, mask).
    trim_frac: float = 0.1
    # trimmed_mean: fraction trimmed from EACH end per coordinate
    # (k = floor(trim_frac · n_alive), clamped so >= 1 value survives).
    krum_f: int = 1
    # krum/multi_krum: assumed number of Byzantine workers f; each
    # worker is scored by its n_alive − f − 2 closest neighbors.
    multi_krum_m: int = 0
    # multi_krum: average the m best-scored workers (0 = auto:
    # n_alive − krum_f).  krum is multi_krum with m = 1.
    clip_radius: float = 0.0
    # Norm clip (0 = off).  Federated: worker updates are clipped to an
    # L2 ball of this radius around theta before aggregation.  Gossip:
    # the clipped-gossip rule — each worker clips every neighbor
    # DEVIATION ``x_j − x_i`` to this radius before applying the mixing
    # weights, so one liar moves any honest worker at most
    # W_ij·clip_radius per round (composes with partition/crash repair,
    # which act on the matrix itself).
    quarantine_after: int = 0
    # Detection/quarantine layer (0 = off): a worker whose update is
    # screened (non-finite, or majority-clipped in gossip) this many
    # rounds IN A ROW is quarantined — masked out via the engines'
    # existing alive/participation machinery and recorded in the fault
    # ledger — then readmitted after ``quarantine_rounds``.
    quarantine_rounds: int = 8  # backoff length before readmission


@dataclass(frozen=True)
class PopulationConfig:
    """Client population registry (``dopt.population``).

    Decouples the client POPULATION (1k–10k host-side client records)
    from the fixed-width device LANES: each round a seeded, stateless
    cohort sampler draws ``cohort`` clients from the eligible
    population, the cohort is bound onto the existing validity-masked
    lanes in ``ceil(cohort / lanes)`` waves, per-device partial
    weighted sums accumulate across the waves, and ONE cross-device
    bucketed reduce (the ``masked_average_scatter`` flat-tree path)
    forms the round's aggregate — so cohort size scales past what the
    lane width (or device memory) can hold in one pass.  Per-client
    state (shard assignment, participation counts, staleness,
    quarantine streaks) lives in host-side arrays keyed by CLIENT id,
    so adversaries and quarantine sentences persist across cohorts.
    ``None`` on ExperimentConfig keeps the exact pre-population
    programs (python-level gating)."""

    clients: int = 1000
    # Population size P: how many client records the registry holds.
    # Clients are stateless FedAvg/FedProx participants (they load
    # theta, train their assigned shard, return an update) — only their
    # registry row persists between the rounds they are sampled in.
    cohort: int = 64
    # Clients sampled per round (M).  When fewer than M clients are
    # eligible (quarantine/churn), the round runs the smaller cohort —
    # cohort size is DATA (lane validity masks), never a shape.
    seed: int | None = None
    # Cohort-sampler seed; None = the experiment seed.  Draws are keyed
    # statelessly by (seed, round), so sampling is bit-reproducible and
    # resume-exact without any persisted RNG state.
    lanes: int | None = None
    # Device lane width per wave (the fixed execution width the cohort
    # is folded onto).  None = ``data.num_users`` (one lane per data
    # shard).  Must divide the device count evenly, like num_users.


@dataclass(frozen=True)
class SeqLMConfig:
    """Sequence-parallel language-model training (``dopt.engine.seqlm``).

    Nothing like it exists in the reference (no attention, no sequence
    axis — SURVEY §2.3); this drives the framework's long-context
    substrate (``dopt.parallel.sequence``) as a real training component:
    a decoder-only TransformerLM with the SEQUENCE axis sharded over the
    mesh and attention running as ring (ppermute KV rotation) or
    Ulysses (all_to_all head resharding) — exact, not approximate."""

    steps: int = 60
    batch: int = 8
    seq_len: int = 512       # divisible by the mesh size
    vocab: int = 64
    dim: int = 128
    depth: int = 2
    heads: int = 4
    attn: str = "ring"       # ring | ulysses | dense (single-device)
    kv_chunk: int = 0
    # ring only: scan each ring block's KV in chunks of this size
    # (flash-style) so per-device score memory is O(block·kv_chunk)
    # instead of O(block²) — the long-sequence memory knob.  0 = whole
    # block at once; must divide seq_len / mesh_size.
    log_every: int = 10


@dataclass(frozen=True)
class CommConfig:
    """Communication substrate schedule (``dopt.parallel.collectives``).

    One knob block shared by BOTH engines: which wire format each flat
    bucket of the ``update_sharding='scatter'`` substrate speaks.  The
    per-bucket schedule (``make_codec_plan``) maps a byte budget onto
    formats — big conv/matmul buckets compress hardest (packed int8 or
    nibble-packed int4 with per-chunk scales and error feedback),
    small norm/bias buckets stay exact — and ``link_byte_budget``
    derives that budget from the lossy-link fault model's goodput.
    ``None`` on ExperimentConfig keeps every pre-change program
    byte-identical (python-level gating)."""

    codec: str = "none"
    # Per-bucket integer codec: "none" | "qsgd" (per-chunk-scaled
    # stochastic int8/int4, dopt.ops.compression.qint_encode).  The
    # gossip engine carries the error-feedback residual as scan state
    # ("comm_residual" in checkpoints); draws are stateless
    # per-(round, bucket, global lane) fold-ins, so compressed runs are
    # bit-reproducible, blocked-exact and resume-exact.
    wire_dtype: str | None = None
    # Dtype narrowing for buckets the codec does NOT cover (and for the
    # whole wire when codec="none"): None | "bfloat16" | "float16".
    byte_budget_mb: float = 0.0
    # Per-lane per-round wire budget in MiB.  0 = no budget: every
    # bucket at least min_codec_bytes large gets the codec at int8.
    # > 0: buckets escalate largest-first (base -> q8 -> q4) until the
    # schedule fits.  Use link_byte_budget(...) to derive it from a
    # FaultConfig's msg_drop/msg_delay rates.
    min_codec_bytes: int = 4096
    # Buckets whose per-lane f32 payload is below this stay at the base
    # wire format — compressing a bias vector saves nothing and costs a
    # scale sidecar.
    chunk: int = 1024
    # Per-lane scale granularity of the integer codec (elements per
    # f32 scale).  Must be even (int4 packs two levels per byte).
    error_feedback: str = "on"
    # "on" | "off": carry the per-bucket quantization residual and fold
    # it back next round (DeepSqueeze/CHOCO error feedback — what keeps
    # aggressive codecs convergent).  "off" drops the residual (an
    # unbiased-codec-only mode for ablations).

    def __post_init__(self) -> None:
        if self.codec not in ("none", "qsgd"):
            raise ValueError(
                f"unknown comm codec {self.codec!r}; one of none|qsgd")
        if self.wire_dtype not in (None, "bfloat16", "float16"):
            raise ValueError(
                f"unknown comm wire_dtype {self.wire_dtype!r}; one of "
                "bfloat16|float16 (or None for the leaf dtype)")
        if self.byte_budget_mb < 0:
            raise ValueError(
                f"comm byte_budget_mb must be >= 0, got "
                f"{self.byte_budget_mb}")
        if self.min_codec_bytes < 0:
            raise ValueError(
                f"comm min_codec_bytes must be >= 0, got "
                f"{self.min_codec_bytes}")
        if self.chunk <= 0 or self.chunk % 2:
            raise ValueError(
                f"comm chunk must be a positive even count, got "
                f"{self.chunk}")
        if self.error_feedback not in ("on", "off"):
            raise ValueError(
                f"unknown comm error_feedback {self.error_feedback!r}; "
                "one of on|off")


@dataclass(frozen=True)
class ExperimentConfig:
    """Top-level experiment description = the notebook form cell, typed."""

    name: str = "experiment"
    seed: int = 2022
    data: DataConfig = field(default_factory=DataConfig)
    model: ModelConfig = field(default_factory=ModelConfig)
    optim: OptimizerConfig = field(default_factory=OptimizerConfig)
    federated: FederatedConfig | None = None
    gossip: GossipConfig | None = None
    seqlm: SeqLMConfig | None = None
    faults: FaultConfig | None = None
    # Fault injection & recovery (dopt.faults.FaultPlan): crashes,
    # stragglers, partitions, Byzantine corruption for the
    # federated/gossip engines.  None = fault-free (bit-identical to a
    # config without the field).
    robust: RobustConfig | None = None
    # Byzantine-robust aggregation & quarantine (dopt.robust).  None =
    # the plain masked-mean programs (bit-identical to pre-robust runs;
    # non-finite updates are still screened from the federated mean).
    population: PopulationConfig | None = None
    # Client population registry (dopt.population): per-round cohort
    # sampling from a 1k–10k client population with hierarchical
    # (multi-wave) aggregation.  None = the classic worker==lane
    # engines, bit-identical to pre-population programs.
    comm: CommConfig | None = None
    # Communication substrate schedule: per-bucket wire codecs inside
    # the scatter path (dopt.parallel.collectives.make_codec_plan).
    # None = the uncompressed wire, bit-identical to pre-comm programs.
    # Execution backend — the pluggable Worker(backend=...) boundary:
    # "jax" runs the TPU/mesh engines; "torch" runs the SAME experiment
    # on the faithful sequential CPU oracle (dopt.engine.torch_backend)
    # — identical init, plans, sampling streams, holdout — for
    # cross-backend trajectory comparison.  Anything else raises.
    backend: str = "jax"
    # Mesh shape: workers are folded onto devices; workers_per_device>1
    # vmaps multiple worker lanes onto one chip (SURVEY §7 hard parts).
    mesh_devices: int | None = None   # None -> all available
    mesh_hosts: int | None = None
    # None -> 1-D worker mesh.  Set to H for a 2-D (hosts × ici) hybrid
    # mesh (dopt.parallel.multihost): on a real multi-slice job the
    # outer axis crosses DCN; single-process it partitions local devices
    # into H virtual hosts (same program, testable anywhere).

    def replace(self, **kw: Any) -> "ExperimentConfig":
        return dataclasses.replace(self, **kw)

    @property
    def num_users(self) -> int:
        return self.data.num_users


def _filter_kwargs(cls: type, d: Mapping[str, Any]) -> dict[str, Any]:
    names = {f.name for f in dataclasses.fields(cls)}
    return {k: v for k, v in d.items() if k in names}


def from_reference_args(args: Mapping[str, Any]) -> ExperimentConfig:
    """Build an ``ExperimentConfig`` from a reference-style flat args dict.

    Accepts the exact key names the reference notebooks use (cells 8/11:
    num_users, local_ep, local_bs, lr, momentum, model, dataset, iid,
    shards, rho, seed, topology, mode, frac, rounds, eps) so published
    experiment dictionaries can be replayed verbatim.
    """
    def _get(key: str, default):
        v = args.get(key)
        return default if v is None else v

    model_name = str(_get("model", "")).lower()
    dataset = str(_get("dataset", "mnist")).lower()
    num_classes = 10
    if dataset in ("cifar", "cifar10"):
        dataset = "cifar10"
        input_shape: tuple[int, ...] = (32, 32, 3)
        default_model = "model3"
    elif dataset == "cifar100":
        input_shape = (32, 32, 3)
        default_model = "model3"
        num_classes = 100
    elif dataset == "a9a":
        input_shape = (123,)   # LIBSVM a9a: 123 binary features, 2 classes
        default_model = "logistic"
        num_classes = 2
    elif dataset == "synthetic":
        input_shape = tuple(_get("input_shape", (28, 28, 1)))
        default_model = "mlp"
    else:
        input_shape = (28, 28, 1)
        default_model = "model1"
    if model_name in ("", "none"):
        model_name = default_model

    if args.get("unequal"):
        raise ValueError(
            "unequal splits are not supported (the reference has none; "
            "both its partitioner families produce equal-size shards)")
    data = DataConfig(
        dataset=dataset,
        iid=bool(_get("iid", True)),
        shards=int(_get("shards", 2)),
        num_users=int(_get("num_users", 8)),
        data_dir=args.get("data_dir"),
    )
    model = ModelConfig(
        model=model_name,
        num_classes=num_classes,
        input_shape=input_shape,
        faithful=bool(_get("faithful", True)),
    )
    optim = OptimizerConfig(
        lr=float(_get("lr", 0.01)),
        momentum=float(_get("momentum", 0.5)),
        rho=float(_get("rho", 0.1)),
        optimizer=str(_get("optimizer", "sgd")),
    )
    federated = None
    gossip = None
    # Reference DotDict form cells carry unused keys with value None;
    # route on a *usable* topology value, not key presence.
    if args.get("topology") or str(_get("paradigm", "")) == "gossip":
        gossip = GossipConfig(
            algorithm=str(_get("algorithm", "dsgd")),
            topology=str(_get("topology", "circle")),
            mode=str(_get("mode", "stochastic")),
            rounds=int(_get("rounds", 10)),
            local_ep=int(_get("local_ep", 4)),
            local_bs=int(_get("local_bs", 128)),
            eps=int(_get("eps", 1)),
        )
    else:
        federated = FederatedConfig(
            algorithm=str(_get("algorithm", "fedavg")),
            frac=float(_get("frac", 0.1)),
            rounds=int(_get("rounds", 20)),
            local_ep=int(_get("local_ep", 10)),
            local_bs=int(_get("local_bs", 50)),
        )
    return ExperimentConfig(
        name=str(args.get("name", "experiment")),
        seed=int(args.get("seed", 2022)),
        data=data,
        model=model,
        optim=optim,
        federated=federated,
        gossip=gossip,
    )


def exp_details(cfg: ExperimentConfig) -> str:
    """Human-readable config dump (reference ``exp_details``, utils.py:147-165)."""
    lines = [f"Experiment: {cfg.name}", f"  seed      : {cfg.seed}", f"  backend   : {cfg.backend}"]
    for section in ("data", "model", "optim", "federated", "gossip", "faults",
                    "robust", "population", "comm"):
        sub = getattr(cfg, section)
        if sub is None:
            continue
        lines.append(f"  [{section}]")
        for f in dataclasses.fields(sub):
            lines.append(f"    {f.name:12s}: {getattr(sub, f.name)}")
    return "\n".join(lines)
