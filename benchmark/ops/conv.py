"""A 2-D convolution, stride and padding already folded into ``out_hw``:
every output position multiplies a ``k x k x cin`` patch into ``cout``
channels.

    macs   = out_h * out_w * k^2 * cin * cout
    params = k^2 * cin * cout + (cout if bias) + (2 * cout if norm)

A normalised convolution carries the norm's scale and bias."""


def macs(layer: dict) -> int:
    h, w = layer["out_hw"]
    return h * w * layer["k"] ** 2 * layer["cin"] * layer["cout"]


def params(layer: dict) -> int:
    n = layer["k"] ** 2 * layer["cin"] * layer["cout"]
    extra = (layer["cout"] if layer["bias"] else 0) + (
        2 * layer["cout"] if layer["norm"] else 0)
    return n + extra
