"""Bytes the compiled round program moves between chips (result buffers
of its collectives): a count from the compiled HLO, compared exactly."""

from benchmark import hlo_bytes


def read(run):
    return float(hlo_bytes.collective_bytes(run.round_hlo)["total"])
