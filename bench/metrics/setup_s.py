"""Seconds from the entry point's first statement to the window's
start: weights, engine, every shape warmed, and the mix's warm seconds
of traffic."""


def read(run):
    return run.t0 - run.t_process
