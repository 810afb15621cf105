"""Drive any Scheduler against the jitted ``repro.core.env`` episode scan.

This is the evaluation half of the trainer's episode loop: no replay, no
updates — just the scheduler's ``select`` inside the (T x N x B) scan, with
queues coupling decisions via Eqn (4).  The same scheduler object (same
carry pytree) can then be handed to ``repro.cluster.live.EdgeCluster`` and
placed against real engines.

With a QoS-enabled ``EnvParams`` (``qos_mix`` set) the scan feeds the
scheduler the extended observation (deadline slack + per-ES affinity) and
``evaluate_scheduler`` reports the same QoS aggregates the live
``summarize()`` produces: per-class p50/p95/p99 delay, deadline-miss
rate, and priority-weighted goodput.

With a fault-enabled ``EnvParams`` (``fault`` set) every ES runs its
Bernoulli up/down chain inside the scan: the observation grows per-ES
availability columns, actions landing on a DOWN server are remapped to
the least-loaded available one with ``penalty_s`` added to that task's
delay, DOWN servers stop draining, and ``evaluate_scheduler`` reports
the ``wrong_choice_rate`` alongside the delay aggregates.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.cluster.schedulers import Scheduler
from repro.core import env as envlib


def build_sim_episode(scheduler: Scheduler, p: envlib.EnvParams) -> Callable:
    """episode(carry, ep_data, key) -> (carry, delays (T,N,B), mask[, wrong]).

    With ``p.has_faults`` the returned callable yields a fourth array —
    per-task wrong-choice flags (the scheduler picked a DOWN server and
    was remapped) of the same (T, N, B) shape.  Without faults the
    availability vector rides the carry as inert ones and every computed
    quantity (observations, RNG stream, delays) is bit-identical to the
    legacy scan.

    Raises ``ValueError`` when the scheduler's layout cannot read
    ``p.layout``'s rows (a block it reads sits elsewhere or is absent).
    """
    if not p.layout.reads(scheduler.layout):
        raise ValueError(f"scheduler {scheduler.name!r} reads "
                         f"{scheduler.layout}; the env's rows are {p.layout}")
    scale = envlib.state_scale(p)

    def episode(carry, ep: envlib.EpisodeData, key):
        qs0 = envlib.init_queues(p)

        def task_step(inner, tn):
            sc, qs, av, key = inner
            t, n = tn
            key, k_sel = jax.random.split(key)
            d = ep.d[t, n]
            workload = ep.rho[t, n] * ep.z[t, n]
            s = envlib.observe(p, qs, d, workload,
                               slack=ep.deadline[t, n],
                               f=ep.f, avail=av) / scale[None, :]
            actions, sc = scheduler.select(sc, s, n, k_sel)
            actions = actions % p.num_bs
            if p.has_faults:
                actions, wrong = envlib.mask_actions(av, qs.q_prev + qs.q_bef,
                                                     actions)
                penalty = p.fault.penalty_s * wrong
            else:
                wrong = jnp.zeros((p.num_bs,), bool)
                penalty = 0.0
            delays = envlib.task_delays(p, ep, qs, t, n, actions) + penalty
            qs = envlib.apply_actions(p, ep, qs, t, n, actions)
            return (sc, qs, av, key), (delays, ep.mask[t, n], wrong)

        def slot_step(inner, t):
            ns = jnp.arange(p.max_tasks)
            inner, per_task = jax.lax.scan(
                task_step, inner, (jnp.full_like(ns, t), ns))
            sc, qs, av, key = inner
            if p.has_faults:
                qs = envlib.end_slot(p, ep, qs, avail=av)
                av = envlib.step_avail(p.fault, av, ep.avail_u[t])
            else:
                qs = envlib.end_slot(p, ep, qs)
            return (sc, qs, av, key), per_task

        av0 = envlib.init_avail(p.num_bs)
        (sc, _, _, _), (delays, mask, wrong) = jax.lax.scan(
            slot_step, (carry, qs0, av0, key), jnp.arange(p.num_slots))
        if p.has_faults:
            return sc, delays, mask, wrong
        return sc, delays, mask

    return episode


def _percentiles(delays: np.ndarray) -> dict:
    if delays.size == 0:
        return {"mean_s": 0.0, "p50_s": 0.0, "p95_s": 0.0, "p99_s": 0.0}
    return {"mean_s": float(delays.mean()),
            "p50_s": float(np.percentile(delays, 50)),
            "p95_s": float(np.percentile(delays, 95)),
            "p99_s": float(np.percentile(delays, 99))}


def evaluate_scheduler(scheduler: Scheduler, p: envlib.EnvParams,
                       episodes: int, key, f: Optional[jnp.ndarray] = None,
                       carry=None) -> dict:
    """Delay percentiles (+ QoS aggregates) over fresh episodes."""
    episode = jax.jit(build_sim_episode(scheduler, p))
    key, k_f = jax.random.split(key)
    if f is None:
        f = envlib.sample_capacities(k_f, p)
    if carry is None:
        carry = scheduler.init_carry()
    all_delays, all_cls, all_dl, all_prio, all_wrong = [], [], [], [], []
    for _ in range(episodes):
        key, k_ep, k_run = jax.random.split(key, 3)
        ep_data = envlib.sample_episode(k_ep, p, f=f)
        res = episode(carry, ep_data, k_run)
        carry, delays, mask = res[0], res[1], res[2]
        sel = np.asarray(mask) > 0
        all_delays.append(np.asarray(delays)[sel])
        all_cls.append(np.asarray(ep_data.cls)[sel])
        all_dl.append(np.asarray(ep_data.deadline)[sel])
        all_prio.append(np.asarray(ep_data.priority)[sel])
        if p.has_faults:
            all_wrong.append(np.asarray(res[3])[sel])
    delays = np.concatenate(all_delays) if all_delays else np.zeros((0,))
    out = {"count": int(delays.size), **_percentiles(delays)}
    # schema parity with the live summarize(): the slot-based sim has no
    # KV model, so cache efficiency is identically zero here — but the
    # keys exist so sim and live records compare column-for-column
    out["prefill_tokens_saved"] = 0
    out["prefix_hit_rate"] = 0.0
    if p.has_faults:
        wrong = (np.concatenate(all_wrong) if all_wrong
                 else np.zeros((0,), bool))
        # sim tasks always complete (wrong picks are remapped + penalised),
        # so the terminal-status schema matches the live summarize() shape
        out.update(completed=int(delays.size), failed=0, abandoned=0,
                   retries=0, completion_rate=1.0,
                   wrong_choice_rate=(float(wrong.mean())
                                      if wrong.size else 0.0))
    if p.has_qos and delays.size:
        cls = np.concatenate(all_cls)
        dl = np.concatenate(all_dl)
        prio = np.concatenate(all_prio)
        missed = delays > dl
        has_dl = np.isfinite(dl)
        out["deadline_miss_rate"] = (float(missed[has_dl].mean())
                                     if has_dl.any() else 0.0)
        out["weighted_goodput"] = float((prio * ~missed).sum()
                                        / max(prio.sum(), 1e-9))
        classes = {}
        for i, (c, _) in enumerate(p.qos_mix):
            m = cls == i
            if not m.any():
                continue
            c_dl = m & has_dl
            classes[c.name] = {
                "count": int(m.sum()),
                "priority": float(c.priority),
                **_percentiles(delays[m]),
                "deadline_miss_rate": (float(missed[c_dl].mean())
                                       if c_dl.any() else 0.0),
                "weighted_goodput": float((prio[m] * ~missed[m]).sum()
                                          / max(prio[m].sum(), 1e-9)),
            }
        out["classes"] = classes
    out["carry"] = carry
    return out
