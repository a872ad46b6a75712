"""A table of ``rows`` vectors of ``dim``, looked up by token id: a
gather, no multiply-add.

    macs   = 0
    params = rows * dim

(The output head of an untied model is a ``matmul`` with ``cout`` = the
vocabulary rows held.)"""


def macs(layer: dict) -> int:
    return 0


def params(layer: dict) -> int:
    return layer["rows"] * layer["dim"]
