"""Host-clock milliseconds per decode round: the time of every engine
``decode`` call of the window (each ends in the argmax readback, so it
includes the device's round), over their number."""


def read(run):
    rounds = [r for r in run.rounds if run.in_window(r.start)
              and run.in_window(r.end)]
    if not rounds:
        return None
    return 1e3 * sum(r.end - r.start for r in rounds) / len(rounds)
