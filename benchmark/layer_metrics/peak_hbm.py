"""Peak device memory of the process on its fullest chip, after the
window (``memory_stats()["peak_bytes_in_use"]``).  It bounds the fleet
that fits, and trades against throughput."""


def read(run):
    if not any(run.peak_bytes):     # the backend reports none (CPU)
        return None
    return max(run.peak_bytes) / 2**30
