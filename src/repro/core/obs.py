"""The scheduler's observation row (paper Eqn 6 and its extensions).

Blocks, always in this order: base ``d, w, q_1..q_E`` (input size,
workload, per-ES backlog); QoS ``slack, c_1..c_E`` (deadline slack, this
task's compute per ES); faults ``a_1..a_E`` (availability); prefix
``hit_1..hit_E`` (expected reusable prompt tokens).  ``core.env`` and
``cluster.live`` build rows with :meth:`ObsLayout.assemble`; schedulers
read them through the layout's named slices.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict

import jax.numpy as jnp
import numpy as np


def _block(name: str) -> property:
    return property(lambda self: self.slices.get(name),
                    doc=f"columns of the ``{name}`` block (None: absent)")


@dataclasses.dataclass(frozen=True)
class ObsLayout:
    """Which blocks a row over ``num_engines`` targets holds, and where."""

    num_engines: int
    qos: bool = False
    fault: bool = False
    prefix: bool = False

    d, w, q = _block("d"), _block("w"), _block("q")
    slack, aff = _block("slack"), _block("aff")
    avail, hit = _block("avail"), _block("hit")

    @functools.cached_property
    def slices(self) -> Dict[str, slice]:
        """Block name -> its columns, in row order."""
        E = self.num_engines
        widths = {"d": 1, "w": 1, "q": E,
                  **({"slack": 1, "aff": E} if self.qos else {}),
                  **({"avail": E} if self.fault else {}),
                  **({"hit": E} if self.prefix else {})}
        ends = np.cumsum(list(widths.values())).tolist()
        return {b: slice(end - n, end)
                for (b, n), end in zip(widths.items(), ends)}

    @property
    def dim(self) -> int:
        return next(reversed(self.slices.values())).stop

    def reads(self, other: "ObsLayout") -> bool:
        """A consumer of layout ``other`` can read a row in this layout:
        every block ``other`` holds sits at the same columns here."""
        return all(self.slices.get(b) == s
                   for b, s in other.slices.items())

    def assemble(self, **blocks):
        """Concatenate the blocks, each ``(..., width)``, along the last
        axis in row order; all-numpy blocks give a numpy row."""
        if blocks.keys() != self.slices.keys():
            raise ValueError(f"{self} holds blocks {list(self.slices)}; "
                             f"got {list(blocks)}")
        parts = [blocks[b] for b in self.slices]
        for (b, cols), x in zip(self.slices.items(), parts):
            if x.shape[-1] != cols.stop - cols.start:
                raise ValueError(f"block {b!r} is {x.shape[-1]} wide in "
                                 f"{self}'s {cols}")
        xp = np if all(isinstance(x, np.ndarray) for x in parts) else jnp
        return xp.concatenate(parts, axis=-1)
