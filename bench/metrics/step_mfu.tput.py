"""Whole-step model FLOP utilization (%); see readers.step_mfu."""
from bench.readers import step_mfu


def read(run):
    return step_mfu(run)
