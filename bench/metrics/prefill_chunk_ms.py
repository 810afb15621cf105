"""Device time per run of the paged prefill program (one chunk), ms."""
from bench.readers import module_ms


def read(run):
    return module_ms(run, "prefill_step")
