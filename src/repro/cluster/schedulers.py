"""Pluggable edge-cluster schedulers: one interface, two backends.

A :class:`Scheduler` maps the paper's Eqn-6 observation rows
``s = [d_n, rho_n*z_n, q_1..q_E]`` (normalised) to target-engine indices.
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

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.core import agents as ag
from repro.core.trainer import LEARNED, make_agent_fns

Carry = Any


class Scheduler:
    """Base: stateless-by-default scheduler over ``num_engines`` targets.

    ``state_dim`` declares the observation width the scheduler was built
    for (``None`` = shape-agnostic).  ``EdgeCluster`` validates it at
    construction: the base Eqn-6 row is ``2 + num_engines`` wide, the
    QoS-extended row ``3 + 2 * num_engines`` (see ``repro.core.env``).
    """

    name = "base"
    state_dim: Optional[int] = None

    def __init__(self, num_engines: int):
        self.num_engines = num_engines

    # -- JAX-traceable batch interface (one row per origin BS) -------------
    def init_carry(self) -> Carry:
        return jnp.zeros((), jnp.int32)

    def select(self, carry: Carry, s: jnp.ndarray, n, key
               ) -> Tuple[jnp.ndarray, Carry]:
        """s (B, state_dim) -> ((B,) int32 engine indices, carry)."""
        raise NotImplementedError

    # -- per-request interface for the live cluster ------------------------
    def select_one(self, carry: Carry, s_row: jnp.ndarray, origin: int,
                   n: int, key) -> Tuple[int, Carry]:
        a, carry = self.select(carry, s_row[None, :], n, key)
        return int(a[0]), carry

    def select_one_masked(self, carry: Carry, s_row: jnp.ndarray,
                          origin: int, n: int, key,
                          avail) -> Tuple[int, Carry]:
        """Availability-masked selection for the live cluster.

        Default policy: take the scheduler's unmasked pick; if it landed
        on a DOWN engine, redirect to the least-loaded available one
        (reading the queue features at obs columns ``2:2+E``) — the live
        twin of the simulator's ``repro.faults.mask_actions``.  The
        caller guarantees at least one engine is available.
        """
        eng, carry = self.select_one(carry, s_row, origin, n, key)
        avail = np.asarray(avail, bool)
        if avail[eng]:
            return eng, carry
        q = np.asarray(s_row, np.float32)[2:2 + self.num_engines]
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
    """Pick the engine with the smallest queue feature (obs columns 2:)."""

    name = "jsq"

    def select(self, carry, s, n, key):
        q = s[:, 2:2 + self.num_engines]
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

    Requires the extended row ``[d, w, q_1..q_E, slack, c_1..c_E]``:
    picks the engine minimising backlog + this task's own expected
    compute there (``q_e + c_e``) — JSQ that actually accounts for
    heterogeneous model/engine speed.  Per-request deadline URGENCY is
    handled where it belongs, in the engines' priority/EDF queues; this
    placement rule maximises the chance the slack survives the queue.
    """

    name = "deadline"

    def __init__(self, num_engines: int):
        super().__init__(num_engines)
        self.state_dim = 3 + 2 * num_engines

    def select(self, carry, s, n, key):
        E = self.num_engines
        q = s[:, 2:2 + E]
        aff = s[:, 3 + E:3 + 2 * E]
        return jnp.argmin(q + aff, axis=-1).astype(jnp.int32), carry


class FailureAwareScheduler(Scheduler):
    """Availability-masked least-work placement on a fault-extended row.

    Requires fault observation: the row's trailing ``E`` columns are the
    per-engine availability features (1 healthy / 0.5 degraded / 0 down)
    appended by ``EdgeCluster.observe`` and by the fault-enabled
    ``core.env`` scan.  Placement is JSQ over the AVAILABLE engines —
    plus this task's expected compute there when the QoS affinity
    columns are present — and DOWN engines are hard-masked, so it never
    pays the simulator's wrong-choice penalty and never strands a live
    request on a dead server.  DEGRADED engines stay eligible but their
    0.5 availability halves their attractiveness via a load inflation.
    """

    name = "failure-aware"

    def __init__(self, num_engines: int, qos: bool = False):
        super().__init__(num_engines)
        self.qos = bool(qos)
        base = 3 + 2 * num_engines if qos else 2 + num_engines
        self.state_dim = base + num_engines

    def select(self, carry, s, n, key):
        E = self.num_engines
        cost = s[:, 2:2 + E]
        if self.qos:
            cost = cost + s[:, 3 + E:3 + 2 * E]
        avail = s[:, -E:]
        # degraded (0.5) engines serve at reduced rate: scale their cost
        cost = cost / jnp.maximum(avail, 0.5)
        cost = jnp.where(avail > 0.25, cost, jnp.inf)
        # all-down column of inf -> argmin returns 0; the live cluster
        # never reaches that case (submit refuses on a total outage)
        return jnp.argmin(cost, axis=-1).astype(jnp.int32), carry


class PrefixAffinityScheduler(Scheduler):
    """Cache-aware placement: route where the prompt's KV already lives.

    Declares ``prefix_obs = True``, so ``EdgeCluster.observe`` appends a
    trailing per-engine block of EXPECTED PREFIX HITS — how many of this
    request's prompt tokens each engine could serve straight from its
    prefix cache (a pure peek; dense engines report 0).  Placement then
    minimises backlog minus a cache credit::

        cost_e = q_e [+ c_e with qos=True] - hit_weight * hit_e

    i.e. earliest-expected-completion where compute ALREADY DONE at an
    engine counts as negative work — the paper's "finish fastest" rule
    once resident state is part of an engine's effective speed.  The
    credit concentrates same-prefix requests on warm engines (raising
    their hit rate further), while the backlog term keeps a hot prefix
    from melting one engine.  With ``fault=True`` the availability
    columns (just before the hit block) mask DOWN engines exactly like
    ``failure-aware``.
    """

    name = "prefix-affinity"
    prefix_obs = True

    def __init__(self, num_engines: int, qos: bool = False,
                 fault: bool = False, hit_weight: float = 0.5):
        super().__init__(num_engines)
        self.qos = bool(qos)
        self.fault = bool(fault)
        self.hit_weight = float(hit_weight)
        base = 3 + 2 * num_engines if self.qos else 2 + num_engines
        self.state_dim = (base + (num_engines if self.fault else 0)
                          + num_engines)

    def select(self, carry, s, n, key):
        E = self.num_engines
        cost = s[:, 2:2 + E]
        if self.qos:
            cost = cost + s[:, 3 + E:3 + 2 * E]
        hit = s[:, -E:]
        if self.fault:
            avail = s[:, -2 * E:-E]
            cost = cost / jnp.maximum(avail, 0.5)
            cost = jnp.where(avail > 0.25, cost, jnp.inf)
        cost = cost - self.hit_weight * hit
        return jnp.argmin(cost, axis=-1).astype(jnp.int32), carry


def _infer_state_dim(states) -> Optional[int]:
    """Observation width a stacked agent pytree was trained on (the
    second-to-last axis of the first critic/Q layer's weights)."""
    for attr in ("c1", "q"):
        net = getattr(states, attr, None)
        if net is not None:
            return int(net[0]["w"].shape[-2])
    return None


class PolicyScheduler(Scheduler):
    """Trained ``repro.core.agents`` policy behind the Scheduler interface.

    ``states`` is the per-BS *stacked* agent pytree exactly as returned by
    ``repro.core.trainer.train_method`` — one agent per origin BS, vmapped
    for batch decisions (the paper's distributed deployment).  The latent
    action store (LAD-TS) keeps evolving inside the carry, so serving
    decisions keep self-conditioning the diffusion chain.
    """

    def __init__(self, method: str, cfg: ag.AgentConfig, states,
                 num_engines: int, n_max: int, greedy: bool = False):
        if method not in LEARNED:
            raise ValueError(f"{method!r} is not a learned method")
        super().__init__(num_engines)
        self.name = method
        self.method = method
        self.cfg = cfg
        self.states = states
        self.n_max = int(n_max)
        self.greedy = greedy
        self.state_dim = _infer_state_dim(states)
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


BASELINES = ("round-robin", "jsq", "random", "local", "deadline",
             "failure-aware", "prefix-affinity")


def make_scheduler(name: str, num_engines: int, **policy_kwargs) -> Scheduler:
    """Factory: baseline by name, or a learned method given agent states.

    ``failure-aware`` accepts ``qos=True`` to read the QoS-extended row;
    ``prefix-affinity`` additionally accepts ``fault=True`` and
    ``hit_weight=`` (cache-credit strength).
    """
    if name == "round-robin":
        return RoundRobinScheduler(num_engines)
    if name == "jsq":
        return JoinShortestQueueScheduler(num_engines)
    if name == "random":
        return RandomScheduler(num_engines)
    if name == "local":
        return LocalOnlyScheduler(num_engines)
    if name == "deadline":
        return DeadlineAwareScheduler(num_engines)
    if name == "failure-aware":
        return FailureAwareScheduler(num_engines,
                                     qos=policy_kwargs.pop("qos", False))
    if name == "prefix-affinity":
        return PrefixAffinityScheduler(
            num_engines, qos=policy_kwargs.pop("qos", False),
            fault=policy_kwargs.pop("fault", False),
            hit_weight=policy_kwargs.pop("hit_weight", 0.5))
    if name in LEARNED:
        return PolicyScheduler(name, num_engines=num_engines,
                               **policy_kwargs)
    raise ValueError(f"unknown scheduler {name!r}; options: "
                     f"{BASELINES + LEARNED}")
