"""Share (%) of the traced slice in which no operation ran on the
device (mean over chips)."""
from bench.readers import idle_share


def read(run):
    return idle_share(run)
