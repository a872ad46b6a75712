"""Pallas TPU kernels: the fused update and mix (``fused_update``) and the
body of the learned sparse attention (``sparse_attention``, which
``dopt.models.decoder`` imports as a module)."""

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
