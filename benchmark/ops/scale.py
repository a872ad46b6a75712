"""A learned vector of ``dim`` applied elementwise: a norm's weight, a
per-head gate's bias.  Elementwise work is left out of the operation
count, as the norms of the convolutional configurations are.

    macs   = 0
    params = dim"""


def macs(layer: dict) -> int:
    return 0


def params(layer: dict) -> int:
    return layer["dim"]
