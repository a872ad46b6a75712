"""Plain forward pass of ResNet-18, CIFAR variant: 3x3 stem of 64
channels, four stages of two basic blocks at 64-128-256-512 channels
(stride 2 entering stages 2-4, 1x1 projection shortcut where the shape
changes), global average pool, linear head; 11,173,962 parameters at 10
classes.  He et al., arXiv:1512.03385 section 4.2 (the CIFAR network
family) with the ImageNet ResNet-18's widths, as the FL literature uses
it.

Departure from the paper, the configuration's own: GroupNorm (32 groups,
eps 1e-6, scale and bias) where the paper has BatchNorm, because batch
statistics are ill-defined under gossip averaging.

Straightforward ``jax.numpy``, float32, one worker at a time; shares no
code with ``dopt/``.  Parameters arrive as the nested dict the program
stores them in (names are data): ``Conv_0``, ``GroupNorm_0``,
``ResidualBlock_0`` .. ``ResidualBlock_7`` (each ``Conv_0``,
``GroupNorm_0``, ``Conv_1``, ``GroupNorm_1`` and, on a shape change,
``Conv_2``, ``GroupNorm_2``), ``head``.
"""

import jax
import jax.numpy as jnp

GROUPS = 32
EPS = 1e-6


def _conv(x, kernel, stride):
    return jax.lax.conv_general_dilated(
        x, kernel, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _group_norm(x, p):
    b, h, w, c = x.shape
    g = min(GROUPS, c)
    xg = x.reshape(b, h, w, g, c // g)
    mean = xg.mean(axis=(1, 2, 4), keepdims=True)
    var = ((xg - mean) ** 2).mean(axis=(1, 2, 4), keepdims=True)
    xg = (xg - mean) / jnp.sqrt(var + EPS)
    return xg.reshape(b, h, w, c) * p["scale"] + p["bias"]


def _block(x, p, stride):
    y = _conv(x, p["Conv_0"]["kernel"], stride)
    y = jnp.maximum(_group_norm(y, p["GroupNorm_0"]), 0.0)
    y = _conv(y, p["Conv_1"]["kernel"], 1)
    y = _group_norm(y, p["GroupNorm_1"])
    if "Conv_2" in p:
        x = _group_norm(_conv(x, p["Conv_2"]["kernel"], stride),
                        p["GroupNorm_2"])
    return jnp.maximum(y + x, 0.0)


def forward(params, x):
    """[B, 32, 32, 3] float32 -> [B, classes] logits."""
    x = _conv(x, params["Conv_0"]["kernel"], 1)
    x = jnp.maximum(_group_norm(x, params["GroupNorm_0"]), 0.0)
    blocks = sorted((k for k in params if k.startswith("ResidualBlock_")),
                    key=lambda k: int(k.rsplit("_", 1)[1]))
    for k in blocks:
        # The first block of every stage but the first halves the map:
        # it is the one whose shortcut needs a projection.
        stride = 2 if "Conv_2" in params[k] else 1
        x = _block(x, params[k], stride)
    x = x.mean(axis=(1, 2))
    return x @ params["head"]["kernel"] + params["head"]["bias"]
