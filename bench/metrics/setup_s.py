"""Seconds from process start until traffic starts: weights made on the
device from the seed, warm-up of every shape the traffic uses (and any
compiling), the scheduler trained and warmed, the traffic generated."""


def read(run):
    return run.setup_s
