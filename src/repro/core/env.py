"""AIGC edge-offloading environment (paper §III, Eqns (1)-(9)).

System model: B base stations, each with an edge server running an AIGC
service.  At each time slot t, N_{b,t} AIGC tasks arrive at BS b; a
scheduler assigns each task to an ES b'.  The service delay of a task
(Eqn 2) is

    T = d_n / v_up  +  rho_n * z_n / f_b'  +  T_wait  +  d~_n / v_down
    T_wait = (q_{t-1,b'} + q_bef) / f_b'                       (Eqn 3)

and per-ES queues evolve by Eqn (4):

    q_t,b' = max(q_{t-1,b'} + sum workloads placed on b' - f_b' * Delta, 0)

AIGC task model: the workload is rho_n * z_n where z_n is the number of
denoising steps demanded (image-quality proxy) and rho_n the cycles per
step — workload depends on model complexity, not input size (paper's
"first challenge").

The environment is fully vectorised JAX: an episode is one (T x N_max x B)
scan; within a slot, the n-th tasks of all B stations are decided
simultaneously against the queue state accumulated from tasks 1..n-1 (the
paper's per-BS parallel / per-task sequential semantics).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.obs import ObsLayout
# re-exported so env consumers drive the fault process through envlib
from repro.faults.simfault import (FaultParams, init_avail,  # noqa: F401
                                   mask_actions, step_avail)


@dataclasses.dataclass(frozen=True)
class EnvParams:
    """Defaults follow Table III of the paper.

    ``qos_mix`` (a tuple of ``(QoSClass, weight)`` pairs, see
    ``repro.workload.qos``) switches on the heterogeneous-QoS extension:
    each task additionally samples a service class (per-class quality
    demand z_n, deadline budget, priority weight), the observation grows
    the QoS block (``repro.core.obs``), rewards are priority-weighted, and
    ``deadline_penalty`` optionally adds a miss penalty to Eqn (9).  With
    an empty mix everything reduces exactly to the paper's setup.

    ``fault`` (a :class:`repro.faults.FaultParams`) switches on the
    availability extension: every ES runs an independent Bernoulli
    up/down chain inside the episode scan, DOWN servers stop draining
    (Eqn 4's ``f`` term gated), the observation grows the availability
    block, and actions landing on a DOWN server are remapped to the
    least-loaded available one with ``penalty_s`` added to the task's
    delay.  ``fault=None`` reproduces the legacy environment
    bit-for-bit, same as ``qos_mix``.
    """

    num_bs: int = 20                 # B
    num_slots: int = 60              # |T|
    slot_seconds: float = 1.0        # Delta
    max_tasks: int = 50              # N_{b,t} ~ U[1, max_tasks]
    min_tasks: int = 1
    # task data size d_n in Mbits ~ U[2, 5]; result size d~_n ~ U[0.6, 1.0]
    d_range: Tuple[float, float] = (2.0, 5.0)
    d_out_range: Tuple[float, float] = (0.6, 1.0)
    # quality demand z_n (denoising steps) ~ U[1, 15]
    z_range: Tuple[float, float] = (1.0, 15.0)
    # computing density rho_n in cycles/step, scaled so workloads are in
    # Gcycles: U[100, 300] cycles/bit-step against Mbit-scale tasks ->
    # rho*z in [0.1, 4.5] Gcycles per task (paper's units).
    rho_range: Tuple[float, float] = (0.1, 0.3)
    # transmission rate v in Mbit/s ~ U[400, 500]
    v_range: Tuple[float, float] = (400.0, 500.0)
    # ES capacity f_b' in Gcycles/s ~ U[10, 50] GHz
    f_range: Tuple[float, float] = (10.0, 50.0)
    # The paper motivates the latent store by tasks having "a specific
    # periodic pattern over a certain period": 0.0 = fully iid tasks,
    # 1.0 = task slot n always carries the same (d, z, rho) demand.
    task_periodicity: float = 0.0
    # QoS extension (repro.workload): () = plain paper env
    qos_mix: Tuple[Tuple[Any, float], ...] = ()
    slack_cap: float = 10.0          # seconds; clamps inf deadlines
    deadline_penalty: float = 0.0    # extra -reward per missed deadline
    # fault extension (repro.faults): None = permanently healthy ESs
    fault: Optional[FaultParams] = None

    @property
    def has_qos(self) -> bool:
        return len(self.qos_mix) > 0

    @property
    def has_faults(self) -> bool:
        return self.fault is not None

    @property
    def z_hi(self) -> float:
        """Largest quality demand across base range and QoS classes."""
        z = self.z_range[1]
        for c, _ in self.qos_mix:
            z = max(z, c.z_range[1])
        return float(z)

    @property
    def layout(self) -> ObsLayout:
        """The observation row (``repro.core.obs``): Eqn 6's
        ``[d_n, rho_n * z_n, q_{t-1,1..B}]``, plus the QoS block when
        classes are active and the availability block when faults are."""
        return ObsLayout(self.num_bs, qos=self.has_qos,
                         fault=self.has_faults)

    @property
    def state_dim(self) -> int:
        return self.layout.dim

    @property
    def action_dim(self) -> int:
        return self.num_bs


class EpisodeData(NamedTuple):
    """Pre-sampled randomness for one episode (shapes lead with T, N, B)."""

    d: jnp.ndarray        # (T, N, B) input Mbits
    d_out: jnp.ndarray    # (T, N, B) result Mbits
    z: jnp.ndarray        # (T, N, B) denoising steps
    rho: jnp.ndarray      # (T, N, B) Gcycles per step
    v_up: jnp.ndarray     # (T, N, B) Mbit/s
    v_down: jnp.ndarray   # (T, N, B) Mbit/s
    mask: jnp.ndarray     # (T, N, B) task exists
    f: jnp.ndarray        # (B,) ES capacity Gcycles/s
    # QoS extension (constants when EnvParams.qos_mix is empty)
    cls: jnp.ndarray      # (T, N, B) int32 class index (0 without QoS)
    deadline: jnp.ndarray  # (T, N, B) service budget, inf = best-effort
    priority: jnp.ndarray  # (T, N, B) priority weight (1 without QoS)
    # fault extension: per-slot uniforms driving the Bernoulli up/down
    # chain (drawn from a folded key, so every legacy field is
    # bit-identical whether or not faults are enabled)
    avail_u: jnp.ndarray  # (T, B) U[0,1)


def sample_capacities(key, p: EnvParams) -> jnp.ndarray:
    """Per-ES compute capacities — hardware, so sampled ONCE per
    environment instance and held fixed across episodes ('reset system
    environment' in Algorithm 1 resets queues, not the cluster)."""
    return jax.random.uniform(key, (p.num_bs,), jnp.float32, *p.f_range)


def sample_episode(key, p: EnvParams, f=None) -> EpisodeData:
    ks = jax.random.split(key, 12)
    shape = (p.num_slots, p.max_tasks, p.num_bs)

    def u(k, lo, hi, s=shape):
        return jax.random.uniform(k, s, jnp.float32, lo, hi)

    def periodic(k_base, k_iid, lo, hi):
        """Blend a per-(task-slot, BS) base demand with iid noise."""
        iid = u(k_iid, lo, hi)
        if p.task_periodicity <= 0.0:
            return iid
        base = jax.random.uniform(k_base, (1, p.max_tasks, p.num_bs),
                                  jnp.float32, lo, hi)
        w = p.task_periodicity
        return w * jnp.broadcast_to(base, shape) + (1 - w) * iid

    n_tasks = jax.random.randint(ks[0], (p.num_slots, p.num_bs),
                                 p.min_tasks, p.max_tasks + 1)
    mask = (jnp.arange(p.max_tasks)[None, :, None]
            < n_tasks[:, None, :]).astype(jnp.float32)
    if p.has_qos:
        classes = [c for c, _ in p.qos_mix]
        w = jnp.asarray([x for _, x in p.qos_mix], jnp.float32)
        cls = jax.random.categorical(ks[11], jnp.log(w / w.sum()),
                                     shape=shape)
        z_lo = jnp.asarray([c.z_range[0] for c in classes], jnp.float32)
        z_hi = jnp.asarray([c.z_range[1] for c in classes], jnp.float32)
        z = jnp.round(z_lo[cls] + u(ks[3], 0.0, 1.0)
                      * (z_hi[cls] - z_lo[cls]))
        deadline = jnp.asarray(
            [c.deadline_s if math.isfinite(c.deadline_s) else jnp.inf
             for c in classes], jnp.float32)[cls]
        priority = jnp.asarray([c.priority for c in classes],
                               jnp.float32)[cls]
    else:
        cls = jnp.zeros(shape, jnp.int32)
        z = jnp.round(periodic(ks[9], ks[3], *p.z_range))
        deadline = jnp.full(shape, jnp.inf, jnp.float32)
        priority = jnp.ones(shape, jnp.float32)
    return EpisodeData(
        d=periodic(ks[8], ks[1], *p.d_range),
        d_out=u(ks[2], *p.d_out_range),
        z=z,
        rho=periodic(ks[10], ks[4], *p.rho_range),
        v_up=u(ks[5], *p.v_range),
        v_down=u(ks[6], *p.v_range),
        mask=mask,
        f=f if f is not None else sample_capacities(ks[7], p),
        cls=cls.astype(jnp.int32),
        deadline=deadline,
        priority=priority,
        avail_u=jax.random.uniform(jax.random.fold_in(key, 0xFA),
                                   (p.num_slots, p.num_bs)),
    )


class QueueState(NamedTuple):
    q_prev: jnp.ndarray   # (B,) queue length at end of slot t-1 (Gcycles)
    q_bef: jnp.ndarray    # (B,) workload placed on each ES so far in slot t


def init_queues(p: EnvParams) -> QueueState:
    z = jnp.zeros((p.num_bs,), jnp.float32)
    return QueueState(q_prev=z, q_bef=z)


def observe(p: EnvParams, qs: QueueState, d, workload,
            slack=None, f=None, avail=None) -> jnp.ndarray:
    """Per-task rows (Eqn 6) in ``p.layout``, vectorised over the B
    stations.  d, workload: (B,) — the n-th task of each BS.  Returns
    (B, state_dim).

    With QoS the rows add the deadline slack (clamped at ``slack_cap``)
    and each ES's expected compute seconds ``workload / f_b'``, which
    make heterogeneous capacities visible to the policy before queues
    build up; with faults, the per-ES availability.
    """
    B = p.num_bs
    blocks = dict(d=d[:, None], w=workload[:, None],
                  q=jnp.broadcast_to(qs.q_prev[None, :], (B, B)))
    if p.has_qos:
        if slack is None or f is None:
            raise ValueError("QoS-enabled EnvParams: observe() needs the "
                             "per-task deadline slack and capacities f")
        blocks.update(slack=jnp.minimum(slack, p.slack_cap)[:, None],
                      aff=workload[:, None] / f[None, :])
    if p.has_faults:
        if avail is None:
            raise ValueError("fault-enabled EnvParams: observe() needs "
                             "the per-ES availability vector")
        blocks["avail"] = jnp.broadcast_to(avail[None, :], (B, B))
    return p.layout.assemble(**blocks)


def task_delays(p: EnvParams, ep: EpisodeData, qs: QueueState, t, n,
                actions: jnp.ndarray) -> jnp.ndarray:
    """Service delay (Eqn 2) of the n-th task of every BS given one-hot-
    index actions (B,) in [0, B).  Returns (B,) delays in seconds."""
    d = ep.d[t, n]                    # (B,)
    z = ep.z[t, n]
    rho = ep.rho[t, n]
    d_out = ep.d_out[t, n]
    v_up = ep.v_up[t, n]
    v_down = ep.v_down[t, n]
    f_tgt = ep.f[actions]             # (B,)
    workload = rho * z                # Gcycles
    t_tx = d / v_up + d_out / v_down
    t_comp = workload / f_tgt
    t_wait = (qs.q_prev[actions] + qs.q_bef[actions]) / f_tgt   # Eqn (3)
    return t_tx + t_comp + t_wait


def apply_actions(p: EnvParams, ep: EpisodeData, qs: QueueState, t, n,
                  actions: jnp.ndarray) -> QueueState:
    """Accumulate the placed workloads into the in-slot queue."""
    workload = ep.rho[t, n] * ep.z[t, n] * ep.mask[t, n]       # (B,)
    placed = jnp.zeros((p.num_bs,), jnp.float32).at[actions].add(workload)
    return QueueState(q_prev=qs.q_prev, q_bef=qs.q_bef + placed)


def end_slot(p: EnvParams, ep: EpisodeData, qs: QueueState,
             avail=None) -> QueueState:
    """Queue update at slot end (Eqn 4).

    With faults enabled the caller passes the per-ES availability vector
    and DOWN servers (avail == 0) drain nothing this slot — their backlog
    carries over untouched until they come back up.
    """
    f = ep.f if avail is None else ep.f * avail
    q = jnp.maximum(qs.q_prev + qs.q_bef - f * p.slot_seconds, 0.0)
    return QueueState(q_prev=q, q_bef=jnp.zeros_like(qs.q_bef))


def state_scale(p: EnvParams) -> jnp.ndarray:
    """Feature normalisation for the networks (keeps inputs O(1))."""
    d_hi = p.d_range[1]
    w_hi = p.rho_range[1] * p.z_hi
    q_hi = p.rho_range[1] * p.z_hi * p.max_tasks  # rough slot load
    B = p.num_bs
    blocks = dict(d=jnp.array([d_hi], jnp.float32),
                  w=jnp.array([w_hi], jnp.float32),
                  q=jnp.full((B,), q_hi, jnp.float32))
    if p.has_qos:
        blocks.update(slack=jnp.array([p.slack_cap], jnp.float32),
                      aff=jnp.full((B,), w_hi / p.f_range[0], jnp.float32))
    if p.has_faults:
        blocks["avail"] = jnp.ones((B,), jnp.float32)
    return p.layout.assemble(**blocks)
