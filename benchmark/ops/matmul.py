"""A ``[cin, cout]`` matrix applied at ``positions`` positions of a sample
(T tokens; default 1), of which this chip holds ``held`` copies (experts;
default 1) and multiplies one position through ``active`` of them
(default 1).

    params = held * (cin * cout + (cout if bias))
    macs   = positions * active * cin * cout

``active`` may be a fraction.  For a chip's share of ``E_pub`` published
experts with ``k`` a token it is ``k * held / E_pub``: the EXPECTATION
under uniform routing of how many of the held experts a token reaches,
not a count of what one batch routed.  The product is rounded to the
nearest multiply-add."""


def macs(layer: dict) -> int:
    return round(layer.get("positions", 1) * layer.get("active", 1)
                 * layer["cin"] * layer["cout"])


def params(layer: dict) -> int:
    return layer.get("held", 1) * (
        layer["cin"] * layer["cout"]
        + (layer["cout"] if layer.get("bias", False) else 0))
