"""repro.workload: QoS classes, mixed traces, EDF queues, capability
descriptors, and the QoS-extended observation across sim + live."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cluster import (EdgeCluster, PolicyScheduler, Request,
                           evaluate_scheduler, make_scheduler,
                           poisson_trace, summarize)
from repro.configs import get_config, reduced
from repro.core.agents import AgentConfig
from repro.core.diffusion import DiffusionPolicyConfig
from repro.core.env import EnvParams, sample_episode
from repro.core.trainer import init_agents
from repro.models.transformer import init_params
from repro.serving.builders import build_fleet
from repro.serving.engine import ServeEngine
from repro.workload import (DEFAULT_MIX, EDFQueue, QoSClass,
                            cold_token_seconds, normalized_weights,
                            qos_poisson_trace, scaled)

ACFG = AgentConfig(train_after=10, replay_capacity=60, batch_size=16,
                   diffusion=DiffusionPolicyConfig(num_steps=2))


def _engine(arch="qwen2-1.5b", num_layers=2, kv_slots=2, max_len=48,
            seed=0, **kw):
    cfg = dataclasses.replace(reduced(get_config(arch)),
                              num_layers=num_layers)
    params = init_params(jax.random.key(seed), cfg)
    return ServeEngine(cfg, params, max_len=max_len, kv_slots=kv_slots,
                      **kw)


def _req(rid, *, qos=None, deadline=None, arrival=0.0, tokens=4):
    prompt = jnp.zeros((1, 4), jnp.int32)
    return Request(rid=rid, prompt=prompt, max_new_tokens=tokens,
                   arrival_s=arrival, qos=qos, deadline_s=deadline)


# ---------------------------------------------------------------------------
# QoS classes + mixed-class traces
# ---------------------------------------------------------------------------


def test_qos_class_validation():
    with pytest.raises(ValueError):
        QoSClass("bad", priority=0.0)
    with pytest.raises(ValueError):
        QoSClass("bad", deadline_s=-1.0)
    with pytest.raises(ValueError):
        QoSClass("bad", z_range=(8, 4))
    c = scaled(QoSClass("ok", deadline_s=2.0), deadline_s=5.0,
               z_range=(2, 4), model_pref="xlstm-350m")
    assert c.deadline_s == 5.0 and c.z_range == (2, 4)
    assert c.model_pref == "xlstm-350m" and not c.best_effort
    classes, w = normalized_weights(DEFAULT_MIX)
    assert len(classes) == 3 and abs(sum(w) - 1.0) < 1e-12


def test_qos_trace_deterministic_given_seed():
    kw = dict(rate=50.0, prompt_len=8, vocab_size=64, num_origins=3,
              seed=7, mix=DEFAULT_MIX)
    a = qos_poisson_trace(20, **kw)
    b = qos_poisson_trace(20, **kw)
    for ra, rb in zip(a, b):
        assert ra.arrival_s == rb.arrival_s
        assert ra.qos.name == rb.qos.name
        assert ra.max_new_tokens == rb.max_new_tokens
        assert ra.deadline_s == rb.deadline_s
        assert ra.origin == rb.origin
        np.testing.assert_array_equal(np.asarray(ra.prompt),
                                      np.asarray(rb.prompt))
    # a different seed must actually change the draw
    c = qos_poisson_trace(20, **{**kw, "seed": 8})
    assert any(ra.arrival_s != rc.arrival_s for ra, rc in zip(a, c))


def test_qos_trace_class_proportions_and_ranges():
    trace = qos_poisson_trace(400, rate=100.0, prompt_len=8,
                              vocab_size=64, mix=DEFAULT_MIX, seed=3)
    classes, w = normalized_weights(DEFAULT_MIX)
    counts = {c.name: 0 for c in classes}
    for r in trace:
        counts[r.qos.name] += 1
        lo, hi = r.qos.z_range
        assert lo <= r.max_new_tokens <= hi
    for c, wi in zip(classes, w):
        assert abs(counts[c.name] / len(trace) - wi) < 0.1, c.name


def test_qos_trace_deadlines_absolute_and_monotone():
    trace = qos_poisson_trace(60, rate=30.0, prompt_len=8,
                              vocab_size=64, mix=DEFAULT_MIX, seed=0)
    by_class = {}
    for r in trace:
        if r.qos.best_effort:
            assert r.deadline_s is None
            continue
        # absolute deadline = arrival + the class budget
        assert abs(r.deadline_s - (r.arrival_s + r.qos.deadline_s)) < 1e-9
        assert abs(r.deadline_budget_s - r.qos.deadline_s) < 1e-9
        by_class.setdefault(r.qos.name, []).append(r.deadline_s)
    assert by_class, "trace drew no deadline-carrying request"
    for name, deadlines in by_class.items():
        # arrivals are time-ordered, so per-class deadlines must be too
        assert deadlines == sorted(deadlines), name


def test_plain_trace_carries_no_qos():
    trace = poisson_trace(5, rate=10.0, prompt_len=8, max_new_tokens=4,
                          vocab_size=64, seed=1)
    for r in trace:
        assert r.qos is None and r.deadline_s is None
        assert r.model_pref is None and r.priority == 1.0
        assert r.deadline_budget_s is None


# ---------------------------------------------------------------------------
# EDF queues + engine-side priority admission
# ---------------------------------------------------------------------------


def test_edf_queue_orders_priority_then_deadline_then_fifo():
    hi = QoSClass("hi", priority=4.0, deadline_s=9.0)
    lo = QoSClass("lo", priority=1.0, deadline_s=9.0)
    q = EDFQueue()
    q.append(_req(0, qos=lo, deadline=5.0))
    q.append(_req(1, qos=hi, deadline=8.0))
    q.append(_req(2, qos=hi, deadline=2.0))
    q.append(_req(3, qos=hi, deadline=2.0, arrival=1.0))
    assert q[0].rid == 2
    assert [q.popleft().rid for _ in range(len(q))] == [2, 3, 1, 0]


def test_edf_queue_degrades_to_fifo_without_qos():
    q = EDFQueue()
    for rid in (3, 1, 4, 1, 5):
        q.append(_req(rid))
    assert [q.popleft().rid for _ in range(len(q))] == [3, 1, 4, 1, 5]
    q.append(_req(9))
    assert len(q) == 1 and bool(q)
    q.clear()
    assert len(q) == 0 and not q
    with pytest.raises(IndexError):
        q.popleft()


def test_engine_serves_high_priority_first():
    """With one dense slot, a high-priority request admitted later must
    still enter service before the queued best-effort ones."""
    engine = _engine(kv_slots=1, paged=False)
    hi = QoSClass("hi", priority=4.0, deadline_s=2.0)
    lo = QoSClass("lo", priority=1.0)
    prompt = jax.random.randint(jax.random.key(0), (1, 8), 0,
                                engine.cfg.vocab_size)
    a = Request(rid=0, prompt=prompt, max_new_tokens=2, qos=lo)
    b = Request(rid=1, prompt=prompt, max_new_tokens=2, qos=lo)
    c = Request(rid=2, prompt=prompt, max_new_tokens=2, qos=hi,
                deadline_s=2.0)
    for r in (a, b, c):
        engine.admit(r)
    done = engine.run_to_completion()
    assert len(done) == 3 and all(r.done for r in (a, b, c))
    # c overtakes b in the queue (a holds the only slot first)
    assert c.t_prefill_start < b.t_prefill_start
    assert c.missed is not None      # finish() resolved the deadline


# ---------------------------------------------------------------------------
# summarize(): robustness + per-class accounting
# ---------------------------------------------------------------------------


def test_summarize_empty_and_unfinished():
    empty = summarize([])
    assert empty["count"] == 0 and empty["unfinished"] == 0
    assert empty["mean_s"] == 0.0 and empty["deadline_miss_rate"] == 0.0
    assert empty["weighted_goodput"] == 0.0

    finished = _req(0, qos=QoSClass("hi", priority=4.0, deadline_s=9.0),
                    deadline=9.0)
    finished.t_enqueue, finished.t_prefill_start = 0.0, 0.1
    finished.t_prefill_end = 0.2
    finished.finish(0.5)
    never_started = _req(1, qos=QoSClass("lo"), tokens=8)
    late = _req(2, qos=QoSClass("hi", priority=4.0, deadline_s=1.0),
                deadline=1.0)
    late.t_enqueue = 0.0
    stats = summarize([finished, never_started, late])
    assert stats["count"] == 1 and stats["unfinished"] == 2
    assert stats["mean_s"] == pytest.approx(0.5)
    # the unfinished deadline-carrying request counts as a miss
    assert stats["deadline_miss_rate"] == pytest.approx(0.5)
    assert stats["weighted_goodput"] == pytest.approx(4.0 / 9.0)
    assert set(stats["classes"]) == {"hi", "lo"}
    assert stats["classes"]["lo"]["unfinished"] == 1
    assert stats["classes"]["hi"]["deadline_miss_rate"] == 0.5


def test_summarize_per_class_percentiles():
    hi = QoSClass("hi", priority=4.0, deadline_s=10.0)
    lo = QoSClass("lo", priority=1.0)
    reqs = []
    for i, (cls, delay) in enumerate([(hi, 0.2), (hi, 0.4), (lo, 2.0)]):
        r = _req(i, qos=cls,
                 deadline=10.0 if not cls.best_effort else None)
        r.t_enqueue, r.t_prefill_start = 0.0, 0.01
        r.t_prefill_end = 0.02
        r.finish(delay)
        reqs.append(r)
    stats = summarize(reqs)
    assert stats["classes"]["hi"]["count"] == 2
    assert stats["classes"]["hi"]["mean_s"] == pytest.approx(0.3)
    assert stats["classes"]["hi"]["max_s"] == pytest.approx(0.4)
    assert stats["classes"]["lo"]["p50_s"] == pytest.approx(2.0)
    assert stats["weighted_goodput"] == pytest.approx(1.0)
    assert stats["deadline_miss_rate"] == 0.0


# ---------------------------------------------------------------------------
# QoS-extended observation: sim env, schedulers, live validation
# ---------------------------------------------------------------------------


def test_env_state_dim_and_episode_shapes_with_qos():
    base = EnvParams(num_bs=3, num_slots=2, max_tasks=2)
    qos = dataclasses.replace(base, qos_mix=DEFAULT_MIX)
    assert base.state_dim == 2 + 3
    assert qos.state_dim == 3 + 2 * 3
    assert qos.has_qos and not base.has_qos
    assert qos.z_hi == max(base.z_range[1],
                           max(c.z_range[1] for c, _ in DEFAULT_MIX))
    ep = sample_episode(jax.random.key(0), qos)
    shape = (qos.num_slots, qos.max_tasks, qos.num_bs)
    assert ep.cls.shape == shape and ep.cls.dtype == jnp.int32
    assert int(ep.cls.max()) < len(DEFAULT_MIX)
    assert ep.deadline.shape == shape and ep.priority.shape == shape
    prios = sorted({c.priority for c, _ in DEFAULT_MIX})
    assert set(np.unique(np.asarray(ep.priority))) <= set(prios)
    # best-effort tasks carry an infinite deadline
    z = np.asarray(ep.z)
    cls = np.asarray(ep.cls)
    for i, (c, _) in enumerate(DEFAULT_MIX):
        m = cls == i
        if m.any():
            assert z[m].min() >= c.z_range[0]
            assert z[m].max() <= c.z_range[1]


def test_env_without_qos_unchanged():
    """The QoS fields must not perturb the legacy sampling path."""
    p = EnvParams(num_bs=2, num_slots=2, max_tasks=2)
    ep = sample_episode(jax.random.key(0), p)
    assert np.all(np.asarray(ep.cls) == 0)
    assert np.all(np.isinf(np.asarray(ep.deadline)))
    assert np.all(np.asarray(ep.priority) == 1.0)


def test_deadline_scheduler_picks_min_queue_plus_affinity():
    s = make_scheduler("deadline", 3)
    assert s.layout.dim == 3 + 2 * 3
    #        d    w    q1   q2   q3  slack aff1 aff2 aff3
    row = [0.5, 0.5, 0.9, 0.1, 0.5, 1.0, 0.0, 0.9, 0.1]
    a, _ = s.select_one(s.init_carry(), jnp.asarray(row), 0, 0,
                        jax.random.key(0))
    assert a == 2      # q+aff = [.9, 1.0, .6]


def test_deadline_scheduler_in_qos_sim():
    p = EnvParams(num_bs=2, num_slots=3, max_tasks=3, qos_mix=DEFAULT_MIX)
    r = evaluate_scheduler(make_scheduler("deadline", 2), p, episodes=1,
                           key=jax.random.key(1))
    assert r["count"] > 0 and r["mean_s"] > 0
    assert 0.0 <= r["deadline_miss_rate"] <= 1.0
    assert 0.0 <= r["weighted_goodput"] <= 1.0
    assert set(r["classes"]) <= {c.name for c, _ in DEFAULT_MIX}
    for st in r["classes"].values():
        assert st["count"] > 0 and st["p99_s"] >= st["p50_s"]


def test_policy_state_dim_inferred_and_validated():
    """A policy is built on the layout it was trained on: weights of any
    other width are refused at construction with both widths named, and
    the live cluster's rows follow the scheduler's layout."""
    base = EnvParams(num_bs=2, num_slots=2, max_tasks=2)
    qos = dataclasses.replace(base, qos_mix=DEFAULT_MIX)
    engines = [_engine(seed=0), _engine(seed=1)]
    for method in ("lad-ts", "dqn-ts"):
        st_base = init_agents(method, base, ACFG, jax.random.key(0))
        st_qos = init_agents(method, qos, ACFG, jax.random.key(0))
        with pytest.raises(ValueError, match="4-wide.* 7 wide"):
            PolicyScheduler(method, ACFG, st_base, qos.layout, n_max=2)
        with pytest.raises(ValueError, match="7-wide.* 4 wide"):
            PolicyScheduler(method, ACFG, st_qos, base.layout, n_max=2)
        s_base = PolicyScheduler(method, ACFG, st_base, base.layout,
                                 n_max=base.max_tasks)
        s_qos = PolicyScheduler(method, ACFG, st_qos, qos.layout,
                                n_max=qos.max_tasks)
        assert s_base.num_engines == s_qos.num_engines == 2
        for s, p in ((s_base, base), (s_qos, qos)):
            cluster = EdgeCluster(engines, s)
            assert cluster.obs_dim == p.state_dim
            assert cluster.observe(_req(0)).shape == (p.state_dim,)
    assert base.state_dim == 4 and qos.state_dim == 7


# ---------------------------------------------------------------------------
# capability descriptors + heterogeneous fleets
# ---------------------------------------------------------------------------


def test_capability_cold_then_measured():
    engine = _engine()
    cap = engine.capability
    assert not cap.measured
    assert cap.arch == "qwen2-1.5b-smoke" or cap.arch == engine.cfg.name
    assert cap.token_seconds == pytest.approx(
        cold_token_seconds(engine.cfg), rel=1e-6)
    assert cap.rho_gcycles == pytest.approx(
        2.0 * engine.cfg.active_param_count() / 1e9)
    prompt = jax.random.randint(jax.random.key(0), (1, 8), 0,
                                engine.cfg.vocab_size)
    engine.admit(Request(rid=0, prompt=prompt, max_new_tokens=3))
    engine.run_to_completion()
    cap2 = engine.capability
    assert cap2.measured and cap2.tok_s > 0
    assert engine.est_token_seconds == pytest.approx(engine._ewma_tok_s)


def test_build_fleet_heterogeneous_archs_and_backends():
    fleet = build_fleet(("qwen2-1.5b", "xlstm-350m"), max_len=32,
                        kv_slots=2)
    assert [e.arch_id for e in fleet] == ["qwen2-1.5b", "xlstm-350m"]
    assert fleet[0].paged and not fleet[1].paged   # attention vs recurrent
    caps = [e.capability for e in fleet]
    assert caps[0].arch != caps[1].arch
    assert all(c.tok_s > 0 for c in caps)


def test_deadline_scheduler_drives_live_qos_cluster():
    """The same DeadlineAwareScheduler object runs the live fleet on the
    extended observation and the trace-level QoS accounting holds up."""
    fleet = build_fleet(("qwen2-1.5b", "xlstm-350m"), max_len=64,
                        kv_slots=2)
    vocab = min(e.cfg.vocab_size for e in fleet)
    mix = ((scaled(QoSClass("fast", priority=4.0, deadline_s=30.0),
                   z_range=(1, 2), model_pref="xlstm-350m"), 0.5),
           (QoSClass("slow", priority=1.0, z_range=(2, 4)), 0.5))
    cluster = EdgeCluster(fleet, make_scheduler("deadline", 2))
    assert cluster.obs_dim == 3 + 2 * 2
    trace = poisson_trace(6, rate=50.0, prompt_len=8, max_new_tokens=4,
                          vocab_size=vocab, num_origins=2, seed=11,
                          qos_mix=mix)
    stats = summarize(cluster.run(trace))
    assert stats["count"] == 6 and stats["unfinished"] == 0
    assert stats["p99_s"] >= stats["p50_s"] > 0
    assert 0.0 <= stats["deadline_miss_rate"] <= 1.0
    assert 0.0 <= stats["weighted_goodput"] <= 1.0
    assert set(stats["classes"]) <= {"fast", "slow"}
