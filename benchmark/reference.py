"""The plain reference the system is held to: loss, gradients, the
momentum-SGD step, the gossip mix and the FedAvg mean, in
straightforward ``jax.numpy`` at float32 and
``jax.default_matmul_precision("highest")``, one worker at a time.

It shares no code with ``dopt/``: it is handed arrays (initial
parameters, the round's batches, the mixing matrix or the sampled
clients) and returns arrays.  Each model is a file of its own under
``reference_models/``, named by the configuration's ``reference`` key:
its ``forward(params, x)`` and, where the training loss is not the
classification loss below, its ``objective(params, x, y, w) -> scalar``
(``load_objective``).

Semantics, as the system documents them and the papers define them:

* loss, where the model's file has no ``objective``: cross-entropy of
  ``log_softmax(model output)`` against the labels, a weighted mean over
  the batch with the plan's 0/1 padding weights
  (``sum(nll*w) / max(sum(w), 1)``).
* loss of a token model (``x`` and ``y`` are ``[B, T]`` ids, the output
  ``[B, T, V]``), the contract its ``objective`` and the program keep so
  that they can agree before either is written (``token_cross_entropy``):
  the plan's 0/1 row weight ``w[b]`` covers every position of row b;
  positions whose label is negative are left out; the loss is the sum of
  the counted positions' negative log-likelihood over their count (at
  least 1).  Whatever the architecture adds to its training loss (a
  router's balance term, with its coefficient) is inside ``objective``,
  and listed in the configuration file under ``assumed`` where the source
  does not give it.
* step: torch-style momentum SGD, ``buf = mu*buf + g; p = p - lr*buf``.
* gossip round (D-SGD, Lian et al. arXiv:1705.09056 with local epochs):
  every worker first replaces its parameters by ``sum_j W[i, j] p_j``
  (momentum is not mixed), then runs its local steps.
* FedAvg round (McMahan et al. arXiv:1602.05629 Alg. 1): each sampled
  client starts from the global model, runs its local steps, and the new
  global model is the plain mean of the sampled clients' parameters.
  Departure, the system's own: a client's momentum buffer persists from
  the last round it was sampled in (the paper has plain SGD).

Memory: between turns every worker's parameters and momentum live on the
host (numpy).  The chip holds one worker's parameters, momentum,
gradients and activations while it steps, and one leaf of the fleet while
that leaf is mixed or averaged, so a fleet of large workers fits where
one of them does.  The arithmetic and its order are those of the fleet
held whole: ``W @ stacked_leaf`` for each leaf, the sampled clients'
leaves added in the order sampled.
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np


def load_module(name: str):
    """``reference_models/<name>.py``."""
    return importlib.import_module(f"benchmark.reference_models.{name}")


def weighted_cross_entropy(outputs, labels, weights):
    logp = jax.nn.log_softmax(outputs, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(nll * weights) / jnp.maximum(jnp.sum(weights), 1.0)


def token_cross_entropy(logits, labels, weights):
    """The token contract above: ``[B, T, V]`` logits, ``[B, T]`` labels
    (negative = not counted), ``[B]`` 0/1 row weights."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(
        logp, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    counted = weights[:, None] * (labels >= 0)
    return jnp.sum(nll * counted) / jnp.maximum(jnp.sum(counted), 1.0)


def objective_of(module):
    """A reference model's training loss ``(params, x, y, w) -> scalar``:
    its own ``objective`` or, where it has none, the classification loss
    of its ``forward``."""
    if hasattr(module, "objective"):
        return module.objective
    forward = module.forward

    def objective(params, x, y, w):
        return weighted_cross_entropy(forward(params, x), y, w)

    return objective


def load_objective(name: str):
    return objective_of(load_module(name))


def make_step(objective, *, lr: float, momentum: float):
    """One jitted local step of one worker: (p, buf, x, y, w) ->
    (p, buf, loss).  The parameters and the momentum handed in are
    donated: the chip holds one copy of each."""

    def step(params, buf, x, y, w):
        loss, grads = jax.value_and_grad(objective)(params, x, y, w)
        buf = jax.tree.map(lambda b, g: momentum * b + g, buf, grads)
        params = jax.tree.map(lambda p, b: p - lr * b, params, buf)
        return params, buf, loss

    return jax.jit(step, donate_argnums=(0, 1))


def _local_steps(step, params, buf, bx, by, bw):
    """One worker's turn: its state goes to the chip, steps through the
    batches and comes back to the host."""
    # A copy of its own (the CPU backend may alias numpy's memory): the
    # step donates what it is handed.
    params, buf = jax.device_put((params, buf), may_alias=False)
    for s in range(bx.shape[0]):
        params, buf, _ = step(params, buf, jnp.asarray(bx[s]),
                              jnp.asarray(by[s]), jnp.asarray(bw[s]))
    return _to_host((params, buf))


def _to_host(tree):
    """Numpy arrays that own their memory: on the CPU backend a fetched
    array is a view that keeps the device's buffer alive."""
    return jax.tree.map(lambda x: x if x.flags.owndata else x.copy(),
                        jax.device_get(tree))


def _f32(tree):
    return jax.tree.map(lambda x: np.asarray(x, np.float32), tree)


def _zeros_like(tree):
    return jax.tree.map(np.zeros_like, tree)


def _leafwise(fn, trees: list) -> list:
    """``fn([leaf of every tree]) -> [leaf of every result]``, one leaf
    of the fleet at a time."""
    treedef = jax.tree.structure(trees[0])
    columns = [fn(list(leaves)) for leaves in
               zip(*(jax.tree.leaves(t) for t in trees))]
    return [treedef.unflatten(row) for row in zip(*columns)]


def _mix_leaf(w, xs):
    """``W @ stacked_leaf``, rows back on the host."""
    return _to_host(jnp.tensordot(w, jnp.asarray(np.stack(xs)), axes=1))


def _mean_leaf(xs):
    """The plain mean, added in the order given."""
    xs = [jnp.asarray(x) for x in xs]
    return [_to_host(sum(xs[1:], xs[0]) / len(xs))]


def run_gossip(objective, init_params, rounds, *, lr, momentum,
               precision="highest"):
    """``rounds`` is a list of ``{"w": [n, n], "bx": [n, S, B, ...],
    "by", "bw"}``.  Every worker starts from ``init_params``.  Returns
    the list of the n workers' final parameter trees (numpy).
    ``precision`` is the ambient matmul precision: ``highest`` is the
    reference; another is a witness (``tests/chip_parity_leaves.py``)."""
    with jax.default_matmul_precision(precision):
        step = make_step(objective, lr=lr, momentum=momentum)
        n = rounds[0]["w"].shape[0]
        params = [_f32(init_params)] * n
        bufs = [_zeros_like(params[0])] * n
        for r in rounds:
            w = jnp.asarray(r["w"], jnp.float32)
            params = _leafwise(lambda xs: _mix_leaf(w, xs), params)
            for i in range(n):
                params[i], bufs[i] = _local_steps(
                    step, params[i], bufs[i], r["bx"][i], r["by"][i],
                    r["bw"][i])
        return params


def run_fedavg(objective, init_params, rounds, *, lr, momentum):
    """``rounds`` is a list of ``{"sel": [m] client ids, "bx": [m, S, B,
    ...], "by", "bw"}`` (row k belongs to client ``sel[k]``).  Returns
    the final global parameter tree (numpy).  A client's momentum is
    made when the client is first sampled."""
    with jax.default_matmul_precision("highest"):
        step = make_step(objective, lr=lr, momentum=momentum)
        theta = _f32(init_params)
        bufs: dict = {}
        for r in rounds:
            locals_ = []
            for k, c in enumerate(r["sel"]):
                buf = bufs[c] if c in bufs else _zeros_like(theta)
                p, bufs[c] = _local_steps(step, theta, buf, r["bx"][k],
                                          r["by"][k], r["bw"][k])
                locals_.append(p)
            theta, = _leafwise(_mean_leaf, locals_)
        return theta


def max_abs_error(a, b) -> float:
    """Largest |a - b| over two parameter trees of one structure."""
    errs = jax.tree.leaves(jax.tree.map(
        lambda x, y: jnp.max(jnp.abs(jnp.asarray(x, jnp.float32)
                                     - jnp.asarray(y, jnp.float32))), a, b))
    return float(max(errs))
