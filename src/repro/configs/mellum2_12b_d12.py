"""Mellum2-12B-A2.5B cut to its first 12 layers: three whole periods of
the (sliding, sliding, sliding, full) pattern, every expert, the whole
vocabulary.  The first stage of a 12/12/4-layer pipeline of v5e chips,
and the most of the model one chip holds with its KV pool.
"""
import dataclasses

from repro.configs.mellum2_12b import CONFIG as FULL

CONFIG = dataclasses.replace(FULL, name="mellum2-12b-a2.5b-d12",
                             num_layers=12)
