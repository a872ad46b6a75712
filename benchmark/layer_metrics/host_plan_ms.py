"""Host milliseconds a round spends building its inputs (batch plan,
mixing matrix or client sample, upload): the program's own
``host_batch_plan`` timer inside the window."""


def read(run):
    if "host_batch_plan" not in run.host_span_s:
        return None
    return 1e3 * run.host_span_s["host_batch_plan"] / run.rounds
