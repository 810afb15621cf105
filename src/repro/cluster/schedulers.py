"""Pluggable edge-cluster schedulers: one interface, two backends.

A :class:`Scheduler` maps the paper's Eqn-6 observation rows, in the
layout it is built with (``repro.core.obs``), to target-engine indices.
Implementations are pure-JAX over an explicit ``carry`` pytree so ONE
scheduler object can drive either

  * the jitted ``repro.core.env`` episode scan (``repro.cluster.simulate``
    vectorises ``select`` over the B base stations inside ``lax.scan``), or
  * a live cluster of continuous-batching ``ServeEngine`` workers
    (``repro.cluster.live`` calls ``select_one`` per arriving request).

``PolicyScheduler`` wraps the trained LAD-TS / D2SAC-TS / SAC-TS / DQN-TS
agent states from ``repro.core.agents`` unmodified; the rest are the
non-learned baselines (round-robin, join-shortest-queue, random,
local-only) the paper ablates against.
"""
from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.core import agents as ag
from repro.core.obs import ObsLayout
from repro.core.trainer import LEARNED, make_agent_fns

Carry = Any


class Scheduler:
    """Base: stateless-by-default scheduler over ``layout.num_engines``
    targets.

    ``layout`` is the observation row the scheduler reads, fixed when it
    is built: ``EdgeCluster`` builds its rows in it, and the simulator
    checks that its environment's rows can be read as it.  ``needs``
    names the blocks beyond the base row that ``select`` reads; a layout
    without one of them is refused here.
    """

    name = "base"
    needs: Tuple[str, ...] = ()

    def __init__(self, layout: ObsLayout):
        missing = [b for b in self.needs if getattr(layout, b) is None]
        if missing:
            raise ValueError(f"scheduler {self.name!r} reads the "
                             f"{', '.join(missing)} block of the row; "
                             f"{layout} has none")
        self.layout = layout

    @property
    def num_engines(self) -> int:
        return self.layout.num_engines

    # -- JAX-traceable batch interface (one row per origin BS) -------------
    def init_carry(self) -> Carry:
        return jnp.zeros((), jnp.int32)

    def select(self, carry: Carry, s: jnp.ndarray, n, key
               ) -> Tuple[jnp.ndarray, Carry]:
        """s (B, layout.dim) -> ((B,) int32 engine indices, carry)."""
        raise NotImplementedError

    # -- per-request interface for the live cluster ------------------------
    def select_one(self, carry: Carry, s_row: jnp.ndarray, origin: int,
                   n: int, key) -> Tuple[int, Carry]:
        a, carry = self.select(carry, s_row[None, :], n, key)
        return int(a[0]), carry

    def select_one_masked(self, carry: Carry, s_row: jnp.ndarray,
                          origin: int, n: int, key,
                          avail) -> Tuple[int, Carry]:
        """Availability-masked selection for the live cluster: the
        unmasked pick, or, if it landed on a DOWN engine, the available
        one with the least queue — the live twin of the simulator's
        ``repro.faults.mask_actions``.  The caller guarantees at least
        one engine is available."""
        eng, carry = self.select_one(carry, s_row, origin, n, key)
        avail = np.asarray(avail, bool)
        if avail[eng]:
            return eng, carry
        q = np.asarray(s_row, np.float32)[self.layout.q]
        return int(np.argmin(np.where(avail, q, np.inf))), carry


class RoundRobinScheduler(Scheduler):
    name = "round-robin"

    def select(self, carry, s, n, key):
        B = s.shape[0]
        a = (carry + jnp.arange(B)) % self.num_engines
        return a.astype(jnp.int32), carry + B


class RandomScheduler(Scheduler):
    name = "random"

    def select(self, carry, s, n, key):
        a = jax.random.randint(key, (s.shape[0],), 0, self.num_engines)
        return a.astype(jnp.int32), carry


class JoinShortestQueueScheduler(Scheduler):
    """Pick the engine with the smallest queue feature."""

    name = "jsq"

    def select(self, carry, s, n, key):
        q = s[:, self.layout.q]
        return jnp.argmin(q, axis=-1).astype(jnp.int32), carry


class LocalOnlyScheduler(Scheduler):
    """Every BS keeps its tasks (no offloading) — the paper's Local-TS."""

    name = "local"

    def select(self, carry, s, n, key):
        return (jnp.arange(s.shape[0]) % self.num_engines).astype(jnp.int32), \
            carry

    def select_one(self, carry, s_row, origin, n, key):
        return int(origin) % self.num_engines, carry


class DeadlineAwareScheduler(Scheduler):
    """Earliest-expected-completion placement on the QoS observation.

    Needs the QoS block ``[slack, c_1..c_E]``: picks the engine
    minimising backlog + this task's own expected compute there
    (``q_e + c_e``) — JSQ that actually accounts for heterogeneous
    model/engine speed.  Per-request deadline URGENCY is handled where it
    belongs, in the engines' priority/EDF queues; this placement rule
    maximises the chance the slack survives the queue.
    """

    name = "deadline"
    needs = ("aff",)

    def select(self, carry, s, n, key):
        cost = s[:, self.layout.q] + s[:, self.layout.aff]
        return jnp.argmin(cost, axis=-1).astype(jnp.int32), carry


def _least_work(layout: ObsLayout, s: jnp.ndarray) -> jnp.ndarray:
    """Backlog (+ this task's compute with the QoS block); with the
    availability block DOWN engines cost inf, degraded (0.5) ones 2x."""
    cost = s[:, layout.q]
    if layout.aff is not None:
        cost = cost + s[:, layout.aff]
    if layout.avail is not None:
        avail = s[:, layout.avail]
        cost = cost / jnp.maximum(avail, 0.5)
        cost = jnp.where(avail > 0.25, cost, jnp.inf)
    return cost


class FailureAwareScheduler(Scheduler):
    """Availability-masked least-work placement on a fault-extended row.

    Needs the availability block (1 healthy / 0.5 degraded / 0 down per
    engine), which ``EdgeCluster.observe`` and the fault-enabled
    ``core.env`` scan both fill.  Placement is JSQ over the AVAILABLE
    engines — plus this task's expected compute there when the layout
    has the QoS block — and DOWN engines are hard-masked, so it never
    pays the simulator's wrong-choice penalty and never strands a live
    request on a dead server.  DEGRADED engines stay eligible but their
    0.5 availability halves their attractiveness via a load inflation.
    """

    name = "failure-aware"
    needs = ("avail",)

    def select(self, carry, s, n, key):
        # all-down column of inf -> argmin returns 0; the live cluster
        # never reaches that case (submit refuses on a total outage)
        cost = _least_work(self.layout, s)
        return jnp.argmin(cost, axis=-1).astype(jnp.int32), carry


class PrefixAffinityScheduler(Scheduler):
    """Cache-aware placement: route where the prompt's KV already lives.

    Needs the prefix block: per engine, the EXPECTED PREFIX HITS — how
    many of this request's prompt tokens it could serve straight from
    its prefix cache (a pure peek; dense engines report 0).  Placement
    then minimises backlog minus a cache credit::

        cost_e = q_e [+ c_e with the QoS block] - hit_weight * hit_e

    i.e. earliest-expected-completion where compute ALREADY DONE at an
    engine counts as negative work — the paper's "finish fastest" rule
    once resident state is part of an engine's effective speed.  The
    credit concentrates same-prefix requests on warm engines (raising
    their hit rate further), while the backlog term keeps a hot prefix
    from melting one engine.  With the availability block DOWN engines
    are masked exactly like ``failure-aware``.
    """

    name = "prefix-affinity"
    needs = ("hit",)

    def __init__(self, layout: ObsLayout, hit_weight: float = 0.5):
        super().__init__(layout)
        self.hit_weight = float(hit_weight)

    def select(self, carry, s, n, key):
        cost = (_least_work(self.layout, s)
                - self.hit_weight * s[:, self.layout.hit])
        return jnp.argmin(cost, axis=-1).astype(jnp.int32), carry


class PolicyScheduler(Scheduler):
    """Trained ``repro.core.agents`` policy behind the Scheduler interface.

    ``states`` is the per-BS *stacked* agent pytree exactly as returned by
    ``repro.core.trainer.train_method`` — one agent per origin BS, vmapped
    for batch decisions (the paper's distributed deployment) — and
    ``layout`` the ``EnvParams.layout`` it was trained on.  The latent
    action store (LAD-TS) keeps evolving inside the carry, so serving
    decisions keep self-conditioning the diffusion chain.
    """

    def __init__(self, method: str, cfg: ag.AgentConfig, states,
                 layout: ObsLayout, n_max: int, greedy: bool = False):
        if method not in LEARNED:
            raise ValueError(f"{method!r} is not a learned method")
        # input width of the first critic (Q-net for DQN) layer
        net = states.q if method == "dqn-ts" else states.c1
        width = int(net[0]["w"].shape[-2])
        if width != layout.dim:
            raise ValueError(
                f"{method} weights read {width}-wide rows, but {layout} "
                f"is {layout.dim} wide: train on an EnvParams whose "
                f"layout this is")
        super().__init__(layout)
        self.name = method
        self.method = method
        self.cfg = cfg
        self.states = states
        self.n_max = int(n_max)
        self.greedy = greedy
        _, act, _, _, _ = make_agent_fns(method, cfg)
        self._act = act
        self._vact = jax.vmap(act, in_axes=(0, 0, None, 0, None))
        self._sel1 = None

    def init_carry(self):
        return self.states

    def select(self, carry, s, n, key):
        keys = jax.random.split(key, s.shape[0])
        a, _, carry = self._vact(carry, s, n % self.n_max, keys, self.greedy)
        return (a % self.num_engines).astype(jnp.int32), carry

    def select_one(self, carry, s_row, origin, n, key):
        if self._sel1 is None:
            greedy = self.greedy

            def sel1(carry, s_row, origin, n, key):
                st = jax.tree_util.tree_map(lambda x: x[origin], carry)
                a, _, st = self._act(st, s_row, n, key, greedy)
                carry = jax.tree_util.tree_map(
                    lambda full, one: full.at[origin].set(one), carry, st)
                return (a % self.num_engines).astype(jnp.int32), carry

            self._sel1 = jax.jit(sel1)
        a, carry = self._sel1(carry, s_row, jnp.int32(origin),
                              jnp.int32(n % self.n_max), key)
        with tracing.span("repro.scheduler.sync"):
            a = int(a)          # the host waits for the device
        return a, carry


_BASELINES = {c.name: c for c in (
    RoundRobinScheduler, JoinShortestQueueScheduler, RandomScheduler,
    LocalOnlyScheduler, DeadlineAwareScheduler, FailureAwareScheduler,
    PrefixAffinityScheduler)}
BASELINES = tuple(_BASELINES)


def make_scheduler(name: str, num_engines: int, qos: bool = False,
                   fault: bool = False, **kwargs) -> Scheduler:
    """Factory: a baseline, or a learned method given agent states, built
    on ``ObsLayout(num_engines, qos, fault)``.

    A baseline's own blocks join the layout: ``deadline`` reads the QoS
    block, ``failure-aware`` the availability block, ``prefix-affinity``
    the prefix block.  ``prefix-affinity`` also takes ``hit_weight=``
    (cache-credit strength); learned methods take ``PolicyScheduler``'s
    ``cfg``, ``states`` and ``n_max``.
    """
    if name in LEARNED:
        return PolicyScheduler(name, layout=ObsLayout(num_engines, qos,
                                                      fault), **kwargs)
    if name not in _BASELINES:
        raise ValueError(f"unknown scheduler {name!r}; options: "
                         f"{BASELINES + LEARNED}")
    layout = ObsLayout(num_engines, qos=qos or name == "deadline",
                       fault=fault or name == "failure-aware",
                       prefix=name == "prefix-affinity")
    return _BASELINES[name](layout, **kwargs)
