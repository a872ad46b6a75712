"""Pallas TPU kernels: the fused update and mix (``fused_update``), the
learned sparse attention's index scores and body (``sparse_attention``),
the held experts' dropless grouped matmul (``grouped_experts``) and the
rotary pass between a decoder attention's projections and the attention
(``rotary``; the last three of which ``dopt.models.decoder`` imports as
modules)."""

from dopt.ops.fused_update import (
    fused_mix_sgd,
    fused_mix_update,
    fused_sgd_momentum,
    fused_sgd_momentum_tree,
    mix_sgd_reference,
    pallas_interpret,
)

__all__ = [
    "fused_mix_sgd",
    "fused_mix_update",
    "fused_sgd_momentum",
    "fused_sgd_momentum_tree",
    "mix_sgd_reference",
    "pallas_interpret",
]
