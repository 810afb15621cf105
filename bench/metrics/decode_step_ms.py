"""Device time per run of the paged decode program (one round), ms."""
from bench.readers import module_ms


def read(run):
    return module_ms(run, "decode_step")
