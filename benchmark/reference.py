"""The plain reference the system is held to: loss, gradients, the
momentum-SGD step, the gossip mix and the FedAvg mean, in
straightforward ``jax.numpy`` at float32 and
``jax.default_matmul_precision("highest")``, one worker at a time.

It shares no code with ``dopt/``: it is handed arrays (initial
parameters, the round's batches, the mixing matrix or the sampled
clients) and returns arrays.  Each model's forward pass is a file of its
own under ``reference_models/``, named by the configuration's
``reference`` key.

Semantics, as the system documents them and the papers define them:

* loss: cross-entropy of ``log_softmax(model output)`` against the
  labels, a weighted mean over the batch with the plan's 0/1 padding
  weights (``sum(nll*w) / max(sum(w), 1)``).
* step: torch-style momentum SGD, ``buf = mu*buf + g; p = p - lr*buf``.
* gossip round (D-SGD, Lian et al. arXiv:1705.09056 with local epochs):
  every worker first replaces its parameters by ``sum_j W[i, j] p_j``
  (momentum is not mixed), then runs its local steps.
* FedAvg round (McMahan et al. arXiv:1602.05629 Alg. 1): each sampled
  client starts from the global model, runs its local steps, and the new
  global model is the plain mean of the sampled clients' parameters.
  Departure, the system's own: a client's momentum buffer persists from
  the last round it was sampled in (the paper has plain SGD).
"""

import importlib

import jax
import jax.numpy as jnp


def load_forward(name: str):
    """``reference_models/<name>.py``'s ``forward(params, x)``."""
    return importlib.import_module(
        f"benchmark.reference_models.{name}").forward


def weighted_cross_entropy(outputs, labels, weights):
    logp = jax.nn.log_softmax(outputs, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(nll * weights) / jnp.maximum(jnp.sum(weights), 1.0)


def make_step(forward, *, lr: float, momentum: float):
    """One jitted local step of one worker: (p, buf, x, y, w) ->
    (p, buf, loss)."""

    def step(params, buf, x, y, w):
        def loss_fn(p):
            return weighted_cross_entropy(forward(p, x), y, w)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        buf = jax.tree.map(lambda b, g: momentum * b + g, buf, grads)
        params = jax.tree.map(lambda p, b: p - lr * b, params, buf)
        return params, buf, loss

    return jax.jit(step)


def _local_steps(step, params, buf, bx, by, bw):
    for s in range(bx.shape[0]):
        params, buf, _ = step(params, buf, jnp.asarray(bx[s]),
                              jnp.asarray(by[s]), jnp.asarray(bw[s]))
    return params, buf


@jax.jit
def _take(stacked, i):
    return jax.tree.map(lambda x: x[i], stacked)


def _f32(tree):
    return jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), tree)


def run_gossip(forward, init_params, rounds, *, lr, momentum):
    """``rounds`` is a list of ``{"w": [n, n], "bx": [n, S, B, ...],
    "by", "bw"}``.  Every worker starts from ``init_params``.  Returns
    the list of the n workers' final parameter trees."""
    with jax.default_matmul_precision("highest"):
        step = make_step(forward, lr=lr, momentum=momentum)
        n = rounds[0]["w"].shape[0]
        params = [_f32(init_params) for _ in range(n)]
        bufs = [jax.tree.map(jnp.zeros_like, params[0]) for _ in range(n)]
        for r in rounds:
            w = jnp.asarray(r["w"], jnp.float32)
            stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *params)
            mixed = jax.tree.map(
                lambda x: jnp.tensordot(w, x, axes=1), stacked)   # W @ x
            for i in range(n):
                params[i], bufs[i] = _local_steps(
                    step, _take(mixed, i), bufs[i], r["bx"][i], r["by"][i],
                    r["bw"][i])
        return params


def run_fedavg(forward, init_params, rounds, num_clients, *, lr, momentum):
    """``rounds`` is a list of ``{"sel": [m] client ids, "bx": [m, S, B,
    ...], "by", "bw"}`` (row k belongs to client ``sel[k]``).  Returns
    the final global parameter tree."""
    with jax.default_matmul_precision("highest"):
        step = make_step(forward, lr=lr, momentum=momentum)
        theta = _f32(init_params)
        bufs = [jax.tree.map(jnp.zeros_like, theta)
                for _ in range(num_clients)]
        for r in rounds:
            locals_ = []
            for k, c in enumerate(r["sel"]):
                p, bufs[c] = _local_steps(step, theta, bufs[c], r["bx"][k],
                                          r["by"][k], r["bw"][k])
                locals_.append(p)
            theta = jax.tree.map(
                lambda *xs: sum(xs[1:], xs[0]) / len(xs), *locals_)
        return theta


def max_abs_error(a, b) -> float:
    """Largest |a - b| over two parameter trees of one structure."""
    errs = jax.tree.leaves(jax.tree.map(
        lambda x, y: jnp.max(jnp.abs(jnp.asarray(x, jnp.float32)
                                     - jnp.asarray(y, jnp.float32))), a, b))
    return float(max(errs))
