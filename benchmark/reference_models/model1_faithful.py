"""Plain forward pass of the FedAvg paper's MNIST CNN as the reference
notebooks build it ("Model1", 1,663,370 parameters): conv 5x5x32 ->
maxpool 2 -> conv 5x5x64 -> maxpool 2 -> fc 512 -> ReLU -> fc 10 ->
softmax.  McMahan et al., arXiv:1602.05629 section 3, "MNIST CNN".

Departures from the paper, both the reference notebooks' own and kept by
the configuration (``faithful`` head): no ReLU after the convolutions,
and the network ends in a softmax whose PROBABILITIES are then fed to a
cross-entropy loss (a double softmax).

Ties inside a pooling window split the gradient equally here (jax's
default); the system routes it to the first winner.  On the seeded
Gaussian data a tie has measure zero.

Straightforward ``jax.numpy``, float32, one worker at a time; shares no
code with ``dopt/``.  Parameters arrive as the nested dict the program
stores them in (names are data): conv1, conv2, fc1, fc2, each with
``kernel`` (HWIO / [in, out]) and ``bias``.
"""

import jax
import jax.numpy as jnp


def _conv_same(x, kernel):
    return jax.lax.conv_general_dilated(
        x, kernel, (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _maxpool2(x):
    b, h, w, c = x.shape
    return x.reshape(b, h // 2, 2, w // 2, 2, c).max(axis=(2, 4))


def forward(params, x):
    """[B, 28, 28, 1] float32 -> [B, 10] probabilities."""
    x = _conv_same(x, params["conv1"]["kernel"]) + params["conv1"]["bias"]
    x = _maxpool2(x)
    x = _conv_same(x, params["conv2"]["kernel"]) + params["conv2"]["bias"]
    x = _maxpool2(x)
    x = x.reshape(x.shape[0], -1)
    x = jnp.maximum(x @ params["fc1"]["kernel"] + params["fc1"]["bias"], 0.0)
    x = x @ params["fc2"]["kernel"] + params["fc2"]["bias"]
    return jax.nn.softmax(x, axis=-1)
