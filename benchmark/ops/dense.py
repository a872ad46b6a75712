"""A dense layer applied once a sample.

    macs   = cin * cout
    params = cin * cout + (cout if bias)

(``matmul`` is the same matrix at T positions a sample, or held in
several copies.)"""


def macs(layer: dict) -> int:
    return layer["cin"] * layer["cout"]


def params(layer: dict) -> int:
    return layer["cin"] * layer["cout"] + (
        layer["cout"] if layer["bias"] else 0)
