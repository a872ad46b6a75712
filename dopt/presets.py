"""Runnable experiment presets.

Two families:

* ``reference_*`` — the reference notebooks' experiment grid, typed
  (P1 ``Primal and Dual Decomposition.ipynb`` cells 8-25: 100 users,
  frac 0.1, 20 rounds, local_ep 10, bs 50, lr 0.1, rho 0.1, IID,
  seed 2022; P2 ``Weighted Average.ipynb`` cells 11-36: 6 users,
  10 rounds, local_ep 4, bs 128, lr 0.01, non-IID shards 2, seed 2028).
* ``baseline_*`` — the five BASELINE.json benchmark configs for the
  north-star targets.

Dataset sizes default to the real datasets' scale; with no raw data on
disk the loaders fall back to shape-compatible synthetic data, so every
preset runs everywhere.
"""

from __future__ import annotations

import dataclasses

from dopt.config import (DataConfig, DecoderConfig, ExperimentConfig,
                         FaultConfig, FederatedConfig, GossipConfig,
                         ModelConfig, OptimizerConfig, PopulationConfig,
                         RobustConfig, SeqLMConfig)

MNIST_TRAIN, MNIST_TEST = 60_000, 10_000
CIFAR_TRAIN, CIFAR_TEST = 50_000, 10_000

# Per-preset throughput-trim compute dtype, chosen by CONTROLLED dtype
# experiment (results/time_to_target.json dtype_control), not by
# assumption: baseline2's corrected-head CNN pays a ~2.7x per-round
# convergence tax in bf16 (0.355 vs 0.664 acc at round 10, identical
# init/batches) that swamps bf16's 1.5x step-time win, so its trim is
# float32; baseline5's GroupNorm ResNet shows no such tax and keeps
# bfloat16.  Presets not listed default to bfloat16.
TRIM_COMPUTE_DTYPE = {"baseline2": "float32", "baseline5": "bfloat16"}


def _mnist_data(num_users: int, iid: bool, shards: int = 2,
                **kw) -> DataConfig:
    return DataConfig(dataset="mnist", num_users=num_users, iid=iid,
                      shards=shards, synthetic_train_size=MNIST_TRAIN,
                      synthetic_test_size=MNIST_TEST, **kw)


def _cifar_data(num_users: int, iid: bool, shards: int = 2) -> DataConfig:
    return DataConfig(dataset="cifar10", num_users=num_users, iid=iid,
                      shards=shards, synthetic_train_size=CIFAR_TRAIN,
                      synthetic_test_size=CIFAR_TEST)


# ---------------------------------------------------------------------
# Reference notebook replays
# ---------------------------------------------------------------------

def reference_federated(algorithm: str = "fedavg") -> ExperimentConfig:
    """P1 notebook setup (cells 8/10): FedAvg/FedProx/FedADMM, 100 users.

    Includes the reference's 90/10 local train/val holdout (each client
    trains on 90% of its shard, P1 clients.py:25-28 — deterministic
    first-10% val split) with per-epoch client history rows."""
    return ExperimentConfig(
        name=f"reference-{algorithm}", seed=2022,
        data=_mnist_data(100, iid=True, local_holdout=0.1,
                         holdout_mode="deterministic"),
        model=ModelConfig(model="model1", faithful=True),
        optim=OptimizerConfig(lr=0.1, momentum=0.5, rho=0.1),
        federated=FederatedConfig(algorithm=algorithm, frac=0.1, rounds=20,
                                  local_ep=10, local_bs=50),
    )


def reference_gossip(algorithm: str = "dsgd", topology: str = "circle",
                     mode: str = "stochastic", iid: bool = False,
                     eps: int = 1) -> ExperimentConfig:
    """P2 notebook setup (cell 11): 6 workers, the topology/mode grid.

    Includes the reference's 90/10 local train/val holdout (P2
    clients.py:20-22 — seeded random val choice) with per-epoch client
    history rows."""
    return ExperimentConfig(
        name=f"reference-{algorithm}-{topology}-{mode}", seed=2028,
        data=_mnist_data(6, iid=iid, local_holdout=0.1,
                         holdout_mode="random"),
        model=ModelConfig(model="model1", faithful=True),
        optim=OptimizerConfig(lr=0.01, momentum=0.5),
        gossip=GossipConfig(algorithm=algorithm, topology=topology, mode=mode,
                            rounds=10, local_ep=4, local_bs=128, eps=eps),
    )


# ---------------------------------------------------------------------
# BASELINE.json benchmark configs
# ---------------------------------------------------------------------

def baseline_1_ring_mnist_mlp() -> ExperimentConfig:
    """4-worker weighted-average consensus, ring mixing, MNIST MLP."""
    return ExperimentConfig(
        name="baseline1-ring-mnist-mlp", seed=2028,
        data=_mnist_data(4, iid=False),
        model=ModelConfig(model="mlp", faithful=False),
        optim=OptimizerConfig(lr=0.05, momentum=0.5),
        gossip=GossipConfig(algorithm="dsgd", topology="circle",
                            mode="metropolis", rounds=20, local_ep=2,
                            local_bs=64),
    )


def baseline_2_dsgd_cifar_cnn() -> ExperimentConfig:
    """16-worker D-SGD, doubly-stochastic mixing, CIFAR-10 small CNN.

    lr/momentum are this repo's choice (BASELINE.json names only the
    workload): 0.05/0.9 blows up model3's logit head in the first round
    (train loss ~1e12, accuracy pinned at chance) on CIFAR-scale inputs;
    0.01/0.5 trains cleanly — pinned by the time_to_target artifact."""
    return ExperimentConfig(
        name="baseline2-dsgd16-cifar-cnn", seed=1,
        data=_cifar_data(16, iid=False),
        model=ModelConfig(model="model3", faithful=False,
                          input_shape=(32, 32, 3)),
        optim=OptimizerConfig(lr=0.01, momentum=0.5),
        gossip=GossipConfig(algorithm="dsgd", topology="circle",
                            mode="double_stochastic", rounds=100, local_ep=1,
                            local_bs=64),
    )


def baseline_3_fedavg_noniid() -> ExperimentConfig:
    """FedAvg primal decomposition, 16 non-IID clients, MNIST."""
    return ExperimentConfig(
        name="baseline3-fedavg16-noniid", seed=2022,
        data=_mnist_data(16, iid=False),
        model=ModelConfig(model="model1", faithful=True),
        optim=OptimizerConfig(lr=0.1, momentum=0.5),
        federated=FederatedConfig(algorithm="fedavg", frac=0.5, rounds=30,
                                  local_ep=5, local_bs=50),
    )


def baseline_4_admm_a9a() -> ExperimentConfig:
    """ADMM dual decomposition, 16 workers, ℓ2-regularised logistic
    regression on a9a (λ = 1e-4 via OptimizerConfig.weight_decay — the
    ℓ2 term is a real loss term, see dopt.models.losses.l2_regulariser)."""
    return ExperimentConfig(
        name="baseline4-admm16-a9a", seed=0,
        data=DataConfig(dataset="a9a", num_users=16, iid=True,
                        synthetic_train_size=32_561,
                        synthetic_test_size=16_281),
        model=ModelConfig(model="logistic", num_classes=2,
                          input_shape=(123,), faithful=False),
        optim=OptimizerConfig(lr=0.05, momentum=0.0, rho=1.0,
                              weight_decay=1e-4),
        federated=FederatedConfig(algorithm="fedadmm", frac=1.0, rounds=50,
                                  local_ep=2, local_bs=128),
    )


def baseline_5_gossip32_resnet() -> ExperimentConfig:
    """32-worker gossip SGD, ResNet-18 CIFAR-10, time-varying random graphs."""
    return ExperimentConfig(
        name="baseline5-gossip32-resnet18", seed=3,
        data=_cifar_data(32, iid=False, shards=4),
        model=ModelConfig(model="resnet18", faithful=False,
                          input_shape=(32, 32, 3)),
        optim=OptimizerConfig(lr=0.1, momentum=0.9),
        # local_bs 128 (not 64): the per-layer roofline showed the
        # grouped-conv fleet program is LANE-BATCH-STARVED at 64 rows —
        # stride-2 / 1x1 / deep-stage convs run at ~0.35x of their
        # single-weight-set rate, recovering to ~0.9x at 128
        # (results/roofline_layers_baseline5.json).  Same samples per
        # round (one epoch over the shard), 23% less device time per
        # round, and measurably better convergence (monotone to 1.0 vs
        # an 0.84-0.93 oscillating plateau at 64 on the synthetic
        # target).
        gossip=GossipConfig(algorithm="dsgd", topology="random",
                            mode="metropolis", rounds=200, local_ep=1,
                            local_bs=128),
    )


def seqlm_ring() -> ExperimentConfig:
    """Sequence-parallel TransformerLM training: ring attention with the
    sequence axis sharded over all available devices (the long-context
    substrate as a driveable component; 1-device meshes fall back to the
    same code path with a 1-block ring).  Synthetic Markov corpus —
    loss falling from log(vocab) toward log(branching) is the learning
    signal (dopt.engine.seqlm.markov_token_stream)."""
    return ExperimentConfig(
        name="seqlm-ring", seed=7,
        model=ModelConfig(model="transformer"),
        optim=OptimizerConfig(lr=0.3, momentum=0.9),
        seqlm=SeqLMConfig(steps=60, batch=8, seq_len=512, vocab=64,
                          dim=128, depth=2, heads=4, attn="ring"),
    )


def laguna_xs2_decoder(**cut) -> DecoderConfig:
    """Laguna-XS.2's published ``config.json``
    (https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json,
    33.4B-A3B) key for key; ``cut`` replaces what a worker holds less of
    (``num_hidden_layers``, ``experts_held``) or, for a toy, any width."""
    period = ("full_attention",) + ("sliding_attention",) * 3
    published = dict(
        model_type="laguna", vocab_size=100352, hidden_size=2048,
        intermediate_size=8192, num_hidden_layers=40,
        num_attention_heads=48, num_key_value_heads=8, head_dim=128,
        max_position_embeddings=262144, attention_bias=False,
        rms_norm_eps=1e-06, num_experts=256, num_experts_per_tok=8,
        moe_intermediate_size=512, shared_expert_intermediate_size=512,
        tie_word_embeddings=False, gating=True, sliding_window=512,
        rope_parameters={
            "full_attention": {
                "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
                "original_max_position_embeddings": 4096, "beta_slow": 1,
                "beta_fast": 64, "attention_factor": 1.4158883083359672,
                "partial_rotary_factor": 0.5},
            "sliding_attention": {
                "rope_type": "default", "rope_theta": 10000,
                "partial_rotary_factor": 1},
            "original_max_position_embeddings": 4096},
        layer_types=period * 10,
        moe_apply_router_weight_on_input=False, partial_rotary_factor=0.5,
        mlp_layer_types=("dense",) + ("sparse",) * 39,
        moe_routed_scaling_factor=2.5,
        num_attention_heads_per_layer=(48, 64, 64, 64) * 10)
    return DecoderConfig(**{**published, **cut})


def laguna_localsgd2(toy: bool = False) -> ExperimentConfig:
    """Two local-SGD workers (arXiv:1805.09767), each one chip's share
    of Laguna-XS.2 (layers 0-4, experts 0-7 of 256 a layer, 12,544 of
    100,352 vocabulary rows: 389.6 M parameters), averaging their
    parameters every round of 8 steps x 1 row of 4,096 Zipf token ids:
    the benchmark cell ``laguna-xs2.localsgd2.t4096`` (needs a 16 GB
    chip).  ``toy=True`` keeps the form (full and sliding layers with 4
    and 8 query heads, a dense layer then experts, 8 of 32 held) at
    widths a CPU trains in seconds."""
    if toy:
        decoder = laguna_xs2_decoder(
            hidden_size=64, intermediate_size=128, head_dim=16,
            num_key_value_heads=4, num_attention_heads_per_layer=(4, 8, 8, 8),
            num_hidden_layers=3, sliding_window=16, num_experts=32,
            num_experts_per_tok=4, moe_intermediate_size=32,
            shared_expert_intermediate_size=32, experts_held=8)
        vocab, seq = 256, 64
    else:
        decoder = laguna_xs2_decoder(num_hidden_layers=5, experts_held=8)
        vocab, seq = 12544, 4096
    return _localsgd2("laguna-localsgd2", decoder, model="laguna", seed=28,
                      vocab=vocab, seq=seq, rows=16, toy=toy)


def _localsgd2(name, decoder, *, model, seed, vocab, seq, rows, toy):
    """Two workers of ``decoder`` on a complete graph with self weight
    (parameters averaged every round, momentum not), ``rows`` token rows
    of ``seq`` ids between them, one row a step, one local epoch a
    round; a toy computes in float32."""
    return ExperimentConfig(
        name=name + "-toy" if toy else name, seed=seed,
        data=DataConfig(dataset="synthetic_tokens", num_users=2, iid=True,
                        synthetic_train_size=rows, synthetic_test_size=2),
        model=ModelConfig(model=model, faithful=False, num_classes=vocab,
                          input_shape=(seq,), decoder=decoder,
                          compute_dtype="float32" if toy else "bfloat16"),
        optim=OptimizerConfig(lr=0.01, momentum=0.9),
        gossip=GossipConfig(algorithm="dsgd", topology="complete",
                            mode="double_stochastic", self_weight=True,
                            rounds=10, local_ep=1, local_bs=1),
        mesh_devices=1,
    )


def keye_vl2_decoder(**cut) -> DecoderConfig:
    """The language model of Keye-VL-2.0-30B-A3B, its published
    ``config.json``
    (https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/config.json,
    ``model_type: KeyeVL2``, 30B-A3B) key for key, the vision tower left
    out; ``cut`` replaces what a worker holds less of
    (``num_hidden_layers``, ``experts_held``) or, for a toy, any width."""
    published = dict(
        model_type="KeyeVL2", vocab_size=151936, hidden_size=2048,
        intermediate_size=6144, num_hidden_layers=48,
        num_attention_heads=32, num_key_value_heads=4, head_dim=128,
        max_position_embeddings=262144, max_window_layers=48,
        attention_bias=False, rms_norm_eps=1e-06, hidden_act="silu",
        num_experts=128, num_local_experts=128, num_experts_per_tok=8,
        moe_intermediate_size=768, norm_topk_prob=True,
        decoder_sparse_step=1, mlp_only_layers=(),
        tie_word_embeddings=False, sliding_window=None,
        use_sliding_window=False, rope_theta=10000000,
        rope_scaling={"mrope_section": [16, 24, 24],
                      "rope_type": "default", "type": "default"},
        sa_config={"indexer_head_dim": 64, "indexer_num_heads": 16,
                   "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                   "q_chunk_size": 512, "topk": 2048})
    return DecoderConfig(**{**published, **cut})


def keye_localsgd2(toy: bool = False) -> ExperimentConfig:
    """Two local-SGD workers, each one chip's share of Keye-VL-2.0's
    language model (layers 0-3, experts 0-7 of 128 a layer, 18,992 of
    151,936 vocabulary rows: 314.4 M parameters), averaging their
    parameters every round of 2 steps x 1 row of 8,192 Zipf token ids
    (the largest H of 1, 2, 4, 8 whose round stays under 3 s on the
    v5e: benchmark/traffic/localsgd2-t8192.json): the benchmark cell
    ``keye-vl2.localsgd2.t8192`` (needs a 16 GB chip).  ``toy=True``
    keeps the form (an indexer of 4 heads that keeps 16 of up to 64
    keys a query, per-head norms, 8 of 32 softmax-routed experts held)
    at widths a CPU trains in seconds."""
    if toy:
        decoder = keye_vl2_decoder(
            hidden_size=64, intermediate_size=128, head_dim=16,
            num_key_value_heads=2, num_attention_heads=4,
            num_hidden_layers=2, num_experts=32, num_local_experts=32,
            num_experts_per_tok=4, moe_intermediate_size=32,
            experts_held=8,
            rope_scaling={"mrope_section": [2, 3, 3],
                          "rope_type": "default", "type": "default"},
            sa_config={"indexer_head_dim": 8, "indexer_num_heads": 4,
                       "indexer_num_kv_heads": 1, "kv_chunk_size": 16,
                       "q_chunk_size": 16, "topk": 16})
        vocab, seq = 256, 64
    else:
        decoder = keye_vl2_decoder(num_hidden_layers=4, experts_held=8)
        vocab, seq = 18992, 8192
    return _localsgd2("keye-localsgd2", decoder, model="decoder", seed=32,
                      vocab=vocab, seq=seq, rows=16 if toy else 4, toy=toy)


PRESETS = {
    "reference-fedavg": lambda: reference_federated("fedavg"),
    "reference-fedprox": lambda: reference_federated("fedprox"),
    "reference-fedadmm": lambda: reference_federated("fedadmm"),
    # SCAFFOLD on the P1 setup — the reference sketches it as dead code
    # (clients.py:146-170); dopt implements the real algorithm.
    "reference-scaffold": lambda: reference_federated("scaffold"),
    "reference-centralized": lambda: reference_gossip("centralized"),
    "reference-nocons-iid": lambda: reference_gossip("nocons", iid=True),
    "reference-nocons-noniid": lambda: reference_gossip("nocons"),
    "reference-dsgd-star": lambda: reference_gossip("dsgd", "star"),
    "reference-dsgd-circle": lambda: reference_gossip("dsgd", "circle"),
    "reference-dsgd-complete": lambda: reference_gossip("dsgd", "complete"),
    "reference-dsgd-circle-double": lambda: reference_gossip(
        "dsgd", "circle", "double_stochastic"),
    "reference-dsgd-complete-double": lambda: reference_gossip(
        "dsgd", "complete", "double_stochastic"),
    # The notebook's "dynamic"-mode run (Weighted Average.ipynb cell 29):
    # args.mode='dynamic' matches NEITHER weight branch in
    # communication_graph (simulators.py:65-85), so the raw 0/1
    # adjacency of the still-'compelete' topology is used as the mixing
    # matrix — unnormalised rows summing to n−1.  mode='ones' is dopt's
    # explicit name for that quirk (dopt.topology; BASELINE.md row 0.32).
    "reference-dsgd-dynamic": lambda: reference_gossip(
        "dsgd", "complete", "ones"),
    "reference-fedlcon": lambda: reference_gossip("fedlcon", eps=5),
    "reference-gossip": lambda: reference_gossip("gossip"),
    "baseline1": baseline_1_ring_mnist_mlp,
    "baseline2": baseline_2_dsgd_cifar_cnn,
    "baseline3": baseline_3_fedavg_noniid,
    "baseline4": baseline_4_admm_a9a,
    "baseline5": baseline_5_gossip32_resnet,
    "seqlm": seqlm_ring,
    "laguna-localsgd2": laguna_localsgd2,
    "laguna-localsgd2-toy": lambda: laguna_localsgd2(toy=True),
    "keye-localsgd2": keye_localsgd2,
    "keye-localsgd2-toy": lambda: keye_localsgd2(toy=True),
    # Fault-injection variants (dopt.faults.FaultPlan): the same
    # workloads under a production-shaped failure regime — per-round
    # client crashes, a straggler deadline finishing half the local
    # work, and occasional 2-way network partitions.  The federated
    # variant over-selects clients FedAvg-paper style so the aggregate
    # still averages ~m survivors.  Tune any knob with
    # --set faults.crash=... or replace wholesale with --faults.
    "baseline3-faulty": lambda: dataclasses.replace(
        baseline_3_fedavg_noniid(),
        name="baseline3-fedavg16-noniid-faulty",
        faults=FaultConfig(crash=0.1, straggle=0.2, straggle_frac=0.5,
                           over_select=0.3, partition=0.05,
                           partition_span=2)),
    "baseline1-faulty": lambda: dataclasses.replace(
        baseline_1_ring_mnist_mlp(),
        name="baseline1-ring-mnist-mlp-faulty",
        faults=FaultConfig(crash=0.1, straggle=0.2, straggle_frac=0.5,
                           partition=0.05, partition_span=2)),
    # Byzantine variants (dopt.faults corrupt kind + dopt.robust): the
    # same workloads with workers that LIE rather than die.  Federated:
    # 3 persistent sign-flipping adversaries (corrupt=1, corrupt_max=3
    # pins workers 0..2) against a coordinate-wise trimmed mean — no
    # quarantine knob, because the federated detection signal is the
    # non-finite screen and sign-flipped updates are finite (it would
    # never fire, while still forcing per-round execution).  Gossip:
    # a scale-mode liar against clipped gossip, where the
    # majority-clipped detection DOES catch finite lies, with a
    # 3-strike quarantine benching it.  Swap the defense with
    # --aggregator / --set robust.*.
    "baseline3-byzantine": lambda: dataclasses.replace(
        baseline_3_fedavg_noniid(),
        name="baseline3-fedavg16-byzantine",
        faults=FaultConfig(corrupt=1.0, corrupt_max=3,
                           corrupt_mode="signflip", corrupt_scale=10.0),
        robust=RobustConfig(aggregator="trimmed_mean", trim_frac=0.25)),
    "baseline1-byzantine": lambda: dataclasses.replace(
        baseline_1_ring_mnist_mlp(),
        name="baseline1-ring-mnist-mlp-byzantine",
        faults=FaultConfig(corrupt=1.0, corrupt_max=1,
                           corrupt_mode="scale", corrupt_scale=50.0),
        robust=RobustConfig(clip_radius=1.0, quarantine_after=3,
                            quarantine_rounds=5)),
    # Degraded-network variants (PR 3): the same workloads over lossy,
    # high-latency links with elastic membership.  Gossip: asymmetric
    # per-edge message loss + bounded-staleness delays + churn, with the
    # push-sum ratio-consensus correction so the fleet still converges
    # to the UNBIASED average (plain gossip under asymmetric loss
    # drifts to a biased one — tests/test_network.py).  Federated: a
    # heavy straggler deadline + lossy/delayed uplinks + churn, with
    # staleness-aware aggregation admitting late updates at decayed
    # weight instead of hard-dropping them.
    "baseline1-lossy": lambda: dataclasses.replace(
        baseline_1_ring_mnist_mlp(),
        name="baseline1-ring-mnist-mlp-lossy",
        gossip=dataclasses.replace(baseline_1_ring_mnist_mlp().gossip,
                                   correction="push_sum"),
        faults=FaultConfig(msg_drop=0.15, msg_delay=0.2, msg_delay_max=2,
                           churn=0.02, churn_span=3, crash=0.05)),
    # Client-scale variant (dopt.population): the baseline3 workload
    # with the worker==lane equation broken — a 1000-client registry
    # sampling a 64-client cohort each round onto the 16 data-shard
    # lanes (4 waves, hierarchical aggregation: per-device partial sums
    # across waves → one bucketed reduce-scatter).  Scale it with
    # --clients/--cohort, e.g. `--clients 10000 --cohort 256`.
    "baseline3-xclients": lambda: dataclasses.replace(
        baseline_3_fedavg_noniid(),
        name="baseline3-fedavg-xclients-1k",
        population=PopulationConfig(clients=1000, cohort=64)),
    "baseline3-elastic": lambda: dataclasses.replace(
        baseline_3_fedavg_noniid(),
        name="baseline3-fedavg16-noniid-elastic",
        federated=dataclasses.replace(baseline_3_fedavg_noniid().federated,
                                      staleness_max=3,
                                      staleness_decay=0.5),
        faults=FaultConfig(straggle=0.5, straggle_frac=0.5,
                           straggler_policy="drop", msg_drop=0.05,
                           msg_delay=0.15, msg_delay_max=3, churn=0.02,
                           churn_span=3, crash=0.05)),
}


def get_preset(name: str) -> ExperimentConfig:
    if name not in PRESETS:
        raise ValueError(f"unknown preset {name!r}; one of {sorted(PRESETS)}")
    return PRESETS[name]()
