"""Host milliseconds a round spends inside the jitted round call until it
returns (argument transfer and launch): the program's ``round_dispatch``
timer inside the window.  A program without that timer reports nothing."""


def read(run):
    if "round_dispatch" not in run.host_span_s:
        return None
    return 1e3 * run.host_span_s["round_dispatch"] / run.rounds
