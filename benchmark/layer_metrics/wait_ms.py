"""Host milliseconds a round spends in ``block_until_ready`` on the round
call's result: the program's ``round_wait`` timer inside the window.  On
one chip it is the device's round less what the dispatch overlapped."""


def read(run):
    if "round_wait" not in run.host_span_s:
        return None
    return 1e3 * run.host_span_s["round_wait"] / run.rounds
