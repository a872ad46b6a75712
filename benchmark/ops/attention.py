"""Causal softmax attention's two products, scores ``Q K^T`` and values
``P V``, for ``heads`` query heads of ``head_dim`` over ``positions``
positions T; ``window`` (null = full) is how many positions back a query
sees, itself included.  Position t (from 0) attends to
``min(t + 1, window or T)`` keys, each costing ``head_dim`` multiply-adds
for its score and ``head_dim`` for its value:

    macs   = 2 * heads * head_dim * sum_t min(t + 1, window or T)
    params = 0

Only the causal triangle (or band) counts: a kernel that multiplies the
masked half does work the algorithm does not need.  The projections are
``matmul`` layers; key/value heads shared by several query heads change
those, not this."""


def macs(layer: dict) -> int:
    t = layer["positions"]
    span = layer.get("window") or t
    attended = sum(min(i + 1, span) for i in range(t))
    return 2 * layer["heads"] * layer["head_dim"] * attended


def params(layer: dict) -> int:
    return 0
