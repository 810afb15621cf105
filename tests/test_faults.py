"""repro.faults: injector determinism, engine crash/recovery invariants,
cluster retry/watchdog semantics, and the fault-enabled simulator."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cluster import (EdgeCluster, FailureAwareScheduler, Request,
                           build_sim_episode, evaluate_scheduler,
                           make_scheduler, poisson_trace, summarize)
from repro.configs import get_config, reduced
from repro.core.env import EnvParams
from repro.core import env as envlib
from repro.core.obs import ObsLayout
from repro.faults import (FaultEvent, FaultInjector, FaultParams, FaultSpec,
                          Health, RetryPolicy, single_crash)
from repro.models.transformer import init_params
from repro.serving.engine import ServeEngine
from repro.workload import INTERACTIVE, STANDARD, BEST_EFFORT
from repro.workload.queueing import EDFQueue

KEY = jax.random.key(0)


def _engine(arch="qwen2-1.5b", num_layers=2, kv_slots=2, max_len=40,
            seed=0, **kw):
    cfg = dataclasses.replace(reduced(get_config(arch)),
                              num_layers=num_layers)
    params = init_params(jax.random.key(seed), cfg)
    return ServeEngine(cfg, params, max_len=max_len, kv_slots=kv_slots,
                       **kw)


def _prompt(engine, n=1, S=8, seed=0):
    return jax.random.randint(jax.random.key(seed), (n, S), 0,
                              engine.cfg.vocab_size)


def _req(rid, prompt, tokens=4, qos=None, deadline_s=None, arrival_s=0.0):
    return Request(rid=rid, prompt=prompt, max_new_tokens=tokens,
                   qos=qos, deadline_s=deadline_s, arrival_s=arrival_s)


# ---------------------------------------------------------------------------
# injector: determinism, auto-recovery, replay
# ---------------------------------------------------------------------------


def test_fault_schedule_deterministic_per_seed():
    spec = FaultSpec(crashes=2, stalls=1, slowdowns=1)
    a = FaultInjector.from_spec(spec, 4, horizon_s=10.0, seed=7)
    b = FaultInjector.from_spec(spec, 4, horizon_s=10.0, seed=7)
    c = FaultInjector.from_spec(spec, 4, horizon_s=10.0, seed=8)
    assert a.describe() == b.describe()
    assert a.describe() != c.describe()
    # every finite-duration crash/slowdown got a matching recover event
    kinds = [e["kind"] for e in a.describe()]
    assert kinds.count("recover") == 3          # 2 crashes + 1 slowdown


def test_injector_fires_each_event_once_and_replays():
    inj = single_crash(engine=1, t_s=1.0, downtime_s=2.0, num_engines=2)
    assert [e.kind for e in inj.due(0.5)] == []
    assert [e.kind for e in inj.due(1.5)] == ["crash"]
    assert [e.kind for e in inj.due(1.5)] == []       # exactly once
    assert [e.kind for e in inj.due(10.0)] == ["recover"]
    assert inj.exhausted
    inj.reset()
    assert not inj.exhausted
    assert [e.kind for e in inj.due(10.0)] == ["crash", "recover"]


def test_injector_rejects_bad_events():
    with pytest.raises(ValueError):
        FaultEvent(t_s=1.0, engine=0, kind="meltdown")
    with pytest.raises(ValueError):
        FaultInjector([FaultEvent(t_s=0.0, engine=5, kind="crash")],
                      num_engines=2)


def test_retry_policy_backoff_and_watchdog():
    rp = RetryPolicy(max_attempts=4, backoff_base_s=0.1, backoff_factor=2.0,
                     deadline_grace=2.0, best_effort_timeout_s=5.0)
    assert rp.backoff_s(1) == pytest.approx(0.1)
    assert rp.backoff_s(3) == pytest.approx(0.4)
    # deadline-carrying request: hopeless past grace * budget
    r = Request(rid=0, prompt=None, max_new_tokens=1, arrival_s=0.0,
                deadline_s=1.0)
    r.t_arrival = 100.0
    assert not rp.hopeless(r, 101.9)
    assert rp.hopeless(r, 102.1)
    # best-effort: flat timeout
    b = Request(rid=1, prompt=None, max_new_tokens=1)
    b.t_arrival = 100.0
    assert not rp.hopeless(b, 104.0)
    assert rp.hopeless(b, 105.1)


# ---------------------------------------------------------------------------
# engine health: crash reclaims KV, degraded modes, shedding
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("paged", [True, False])
def test_crash_mid_prefill_and_mid_decode_reclaims_kv(paged):
    """kv_leak must return to 0 after a crash at ANY lifecycle point,
    for both the paged page pool and the dense slot pool."""
    kw = {"prefill_chunk": 4} if paged else {}
    e = _engine(paged=paged, kv_slots=2, max_len=40, **kw)
    prompts = _prompt(e, 2, 12)
    reqs = [_req(i, prompts[i:i + 1], tokens=8) for i in range(2)]
    for r in reqs:
        e.admit(r)
    e.step()                      # paged: mid-prefill; dense: mid-decode
    assert e.kv_leak > 0          # KV actually held before the crash
    orphans = e.fail("test crash mid-prefill/decode")
    assert e.kv_leak == 0
    assert e.health is Health.DOWN
    assert sorted(r.rid for r in orphans) == [0, 1]

    # crash mid-decode after recovery
    e.recover()
    for r in orphans:
        r.reset_for_retry()
        e.admit(r)
    for _ in range(4):
        e.step()                  # prefill done, several decode rounds
    assert e.kv_leak > 0
    orphans = e.fail("test crash mid-decode")
    assert e.kv_leak == 0
    assert len(orphans) == 2
    # full recovery: the SAME requests complete cleanly afterwards
    e.recover()
    for r in orphans:
        r.reset_for_retry()
        e.admit(r)
    done = e.run_to_completion()
    assert sorted(r.rid for r in done) == [0, 1]
    assert all(len(r.tokens) == r.max_new_tokens for r in done)
    assert e.kv_leak == 0


def test_crash_mid_prefill_shared_prefix_reclaims_only_unshared():
    """A crash mid-prefill on a lane that shares a cached prefix must
    drop ONLY that lane's references: cached pages stay resident (the
    cache's own refs), refcounts return to exactly one-per-entry
    (kv_leak == 0), and the retried request hits the cache again."""
    e = _engine(paged=True, kv_slots=2, max_len=64, page_size=8,
                prefill_chunk=8)
    prompt = _prompt(e, 1, 24, seed=42)
    done = e.generate(prompt, 2)               # seed the prefix cache
    assert len(done.tokens) == 2
    cached = e.prefix_cached_pages
    assert cached == 3                         # 24 tokens / page 8
    r = _req(1, prompt, tokens=4)
    e.admit(r)
    e.step()                                   # mid-prefill, prefix shared
    assert r.prefix_tokens == 23               # 2 full pages + 7 COW
    assert e.kv_leak > 0                       # lane refs actually held
    orphans = e.fail("crash mid-prefill on shared prefix")
    assert orphans == [r]
    assert e.kv_leak == 0                      # only unshared refs dropped
    assert e.prefix_cached_pages == cached     # cache intact through crash
    assert e._pool.total_refs == cached        # exactly 1 ref per entry
    e.recover()
    r.reset_for_retry()
    saved0 = e.prefill_tokens_saved
    e.admit(r)
    out = e.run_to_completion()
    assert [x.rid for x in out] == [1]
    assert len(r.tokens) == 4
    assert e.prefill_tokens_saved == saved0 + 23   # retry hit the cache
    assert e.kv_leak == 0


def test_down_engine_rejects_admission_and_degraded_modes():
    e = _engine(paged=False, kv_slots=1)
    e.fail("boom")
    with pytest.raises(RuntimeError, match="DOWN"):
        e.admit(_req(0, _prompt(e), tokens=2))
    assert e.availability == 0.0 and not e.available
    e.recover()
    assert e.health is Health.HEALTHY and e.availability == 1.0
    # stall: frozen, then self-heals
    clock = [0.0]
    e._clock = lambda: clock[0]
    e.degrade(stall_s=5.0)
    assert e.availability == 0.5 and e.available
    e.admit(_req(1, _prompt(e), tokens=1))
    assert e.step() == []          # frozen
    clock[0] = 6.0
    done = e.run_to_completion()
    assert [r.rid for r in done] == [1]
    assert e.health is Health.HEALTHY       # stall self-healed


def test_degrade_down_engine_raises():
    e = _engine(paged=False, kv_slots=1)
    e.fail("boom")
    with pytest.raises(RuntimeError):
        e.degrade(stall_s=1.0)


# ---------------------------------------------------------------------------
# EDF re-entry and queue shedding
# ---------------------------------------------------------------------------


def test_orphans_reenter_edf_queue_in_priority_deadline_order():
    """Requests orphaned by a crash re-enter another engine's EDF queue
    and drain in (priority desc, deadline asc) order regardless of the
    order the crash emitted them."""
    a = _engine(paged=False, kv_slots=1, seed=0)
    b = _engine(paged=False, kv_slots=1, seed=1)
    p = _prompt(a)
    reqs = [
        _req(0, p, qos=BEST_EFFORT),
        _req(1, p, qos=INTERACTIVE, deadline_s=2.0),
        _req(2, p, qos=STANDARD, deadline_s=6.0),
        _req(3, p, qos=INTERACTIVE, deadline_s=1.0),
    ]
    for r in reqs:
        a.admit(r)
    orphans = a.fail("crash")
    assert len(orphans) == 4
    for r in orphans:
        r.reset_for_retry()
        b.admit(r)
    order = []
    while b._queue:
        order.append(b._queue.popleft().rid)
    # interactive (prio 4) by deadline, then standard, then batch
    assert order == [3, 1, 2, 0]


def test_edf_drain_preserves_surviving_order():
    q = EDFQueue()
    p = None
    reqs = [_req(i, p, qos=[INTERACTIVE, STANDARD, BEST_EFFORT][i % 3],
                 deadline_s=float(10 - i)) for i in range(6)]
    for r in reqs:
        q.append(r)
    shed = q.drain(lambda r: r.qos is BEST_EFFORT)
    assert sorted(r.rid for r in shed) == [2, 5]
    survivors = []
    while q:
        survivors.append(q.popleft())
    keys = [(-r.priority, r.deadline_s) for r in survivors]
    assert keys == sorted(keys)


# ---------------------------------------------------------------------------
# cluster: crash recovery, retries, watchdog, quarantine
# ---------------------------------------------------------------------------


def _cluster_pair(**kw):
    engines = [_engine(paged=False, kv_slots=2, seed=i) for i in range(2)]
    sched = make_scheduler("jsq", 2)
    return engines, EdgeCluster(engines, sched, **kw)


def test_cluster_crash_recovery_no_duplicate_completions():
    """Mid-trace crash: all requests complete exactly once, token streams
    are whole, KV accounting returns to zero, attempts are bounded."""
    engines, cluster = _cluster_pair(
        faults=single_crash(engine=0, t_s=0.02, downtime_s=0.2,
                            num_engines=2),
        retry=RetryPolicy())
    vocab = engines[0].cfg.vocab_size
    trace = poisson_trace(10, rate=100.0, prompt_len=8, max_new_tokens=4,
                          vocab_size=vocab, num_origins=2, seed=5)
    done = cluster.run(trace)
    assert len(done) == 10                       # each request exactly once
    assert len({r.rid for r in done}) == 10      # no duplicates
    st = summarize(done)
    assert st["completion_rate"] == 1.0
    assert st["completed"] == 10 and st["failed"] == 0
    for r in done:
        assert r.status == "ok"
        assert 1 <= r.attempts <= cluster.retry.max_attempts
        assert len(r.tokens) == r.max_new_tokens     # no torn streams
    assert all(e.kv_leak == 0 for e in engines)
    assert cluster.fault_stats["injected"] == 2      # crash + recover
    if cluster.fault_stats["orphaned"]:
        assert st["retries"] >= 1
        assert cluster.fault_stats["orphan_recovery_s"]


def test_cluster_submit_raises_on_total_outage():
    engines, cluster = _cluster_pair()
    for e in engines:
        e.fail("both down")
    with pytest.raises(RuntimeError, match="all 2 engines are DOWN"):
        cluster.submit(_req(0, _prompt(engines[0]), tokens=2))


def test_cluster_quarantines_throwing_engine(monkeypatch):
    """An exception escaping one engine's step() marks it DOWN and
    re-offloads its requests instead of unwinding the closed loop."""
    engines, cluster = _cluster_pair(retry=RetryPolicy())
    vocab = engines[0].cfg.vocab_size

    def explode():
        raise RuntimeError("synthetic engine fault")

    # the overlapped cluster path enters through dispatch(); the serial
    # fallback through step() — explode both
    monkeypatch.setattr(engines[0], "step", explode)
    monkeypatch.setattr(engines[0], "dispatch", explode)
    trace = poisson_trace(6, rate=200.0, prompt_len=8, max_new_tokens=3,
                          vocab_size=vocab, num_origins=2, seed=2)
    done = cluster.run(trace)
    assert engines[0].health is Health.DOWN
    assert "quarantined" in engines[0].fail_reason
    assert cluster.fault_stats["quarantined"] == 1
    st = summarize(done)
    assert st["completion_rate"] == 1.0          # engine 1 absorbed all
    assert len(done) == 6


def test_watchdog_abandons_hopeless_never_counts_delay():
    """A request whose deadline is hopeless is abandoned (status stamped,
    no t_finish) and never enters the delay percentiles."""
    engines, cluster = _cluster_pair(retry=RetryPolicy(
        best_effort_timeout_s=0.001, deadline_grace=1.0))
    e = engines[0]
    # park a best-effort request in the retry queue in the past
    r = _req(0, _prompt(e), tokens=2)
    r.t_arrival = cluster._clock() - 10.0        # long overdue
    cluster._park(r, cluster._clock() - 1.0)
    done = cluster.step()
    assert [x.status for x in done] == ["abandoned"]
    assert r.t_finish is None
    st = summarize([r])
    assert st["abandoned"] == 1 and st["count"] == 0
    assert st["p99_s"] == 0.0                    # nothing entered delays
    assert st["completion_rate"] == 1.0          # shed, not failed


def test_retries_exhausted_marks_failed():
    engines, cluster = _cluster_pair(retry=RetryPolicy(max_attempts=2))
    r = _req(0, _prompt(engines[0]), tokens=2)
    r.t_arrival = cluster._clock()
    r.attempts = 2                               # already placed twice
    out = cluster._requeue(r, cluster._clock())
    assert out == [r] and r.status == "failed"
    assert "retries exhausted" in r.fail_reason
    assert cluster.fault_stats["failed"] == 1


def test_fault_free_cluster_has_no_watchdog_side_effects():
    """Without faults= / retry= the watchdog must never shed — the
    fault-free cluster behaves exactly like the pre-fault one."""
    engines, cluster = _cluster_pair()
    assert not cluster._watchdog
    r = _req(0, _prompt(engines[0]), tokens=2, deadline_s=1e-9)
    r.t_arrival = cluster._clock() - 100.0       # hopeless by any watchdog
    engines[0].admit(r)
    assert cluster._shed_hopeless(cluster._clock()) == []
    assert r.status == "pending"


# ---------------------------------------------------------------------------
# fault-enabled simulator
# ---------------------------------------------------------------------------


def test_env_legacy_parity_with_empty_fault_config():
    """fault=None and FaultParams(p_down=0) produce bit-identical delay
    statistics — the availability extension is provably inert when off."""
    p0 = EnvParams(num_bs=3, num_slots=5, max_tasks=4)
    pf = dataclasses.replace(p0, fault=FaultParams(p_down=0.0, p_up=1.0))
    r0 = evaluate_scheduler(make_scheduler("jsq", 3), p0, 2, KEY)
    rf = evaluate_scheduler(make_scheduler("jsq", 3), pf, 2, KEY)
    for k in ("mean_s", "p50_s", "p95_s", "p99_s", "count"):
        assert r0[k] == rf[k], k
    assert rf["wrong_choice_rate"] == 0.0
    assert rf["completion_rate"] == 1.0


def test_env_fault_state_dim_and_observe_guard():
    p = EnvParams(num_bs=3, fault=FaultParams())
    assert p.state_dim == 2 + 3 + 3
    assert envlib.state_scale(p).shape == (p.state_dim,)
    qs = envlib.init_queues(p)
    d = jnp.ones((3,))
    with pytest.raises(ValueError, match="availability"):
        envlib.observe(p, qs, d, d)
    s = envlib.observe(p, qs, d, d, avail=jnp.array([1.0, 0.0, 1.0]))
    assert s.shape == (3, p.state_dim)
    np.testing.assert_array_equal(np.asarray(s[:, -3:]),
                                  np.tile([1.0, 0.0, 1.0], (3, 1)))


def test_step_avail_transitions_and_mask_actions():
    fp = FaultParams(p_down=0.5, p_up=0.5)
    avail = jnp.array([1.0, 1.0, 0.0, 0.0])
    u = jnp.array([0.4, 0.6, 0.4, 0.6])      # < p triggers a transition
    out = np.asarray(envlib.step_avail(fp, avail, u))
    np.testing.assert_array_equal(out, [0.0, 1.0, 1.0, 0.0])
    # masking: picks on DOWN engines remap to least-loaded UP engine
    load = jnp.array([5.0, 1.0, 0.0])
    actions = jnp.array([2, 0, 1], jnp.int32)
    masked, wrong = envlib.mask_actions(jnp.array([1.0, 1.0, 0.0]), load,
                                        actions)
    np.testing.assert_array_equal(np.asarray(masked), [1, 0, 1])
    np.testing.assert_array_equal(np.asarray(wrong), [True, False, False])
    # all-down: picks stand, nothing is penalised
    masked, wrong = envlib.mask_actions(jnp.zeros(3), load, actions)
    np.testing.assert_array_equal(np.asarray(masked), np.asarray(actions))
    assert not np.asarray(wrong).any()


def test_sim_down_engines_dont_drain():
    p = EnvParams(num_bs=2, fault=FaultParams())
    ep = envlib.sample_episode(KEY, p)
    qs = envlib.QueueState(q_prev=jnp.array([4.0, 4.0]),
                           q_bef=jnp.zeros(2))
    out = envlib.end_slot(p, ep, qs, avail=jnp.array([1.0, 0.0]))
    q = np.asarray(out.q_prev)
    assert q[0] < 4.0                      # healthy engine drained
    assert q[1] == 4.0                     # DOWN engine carried over


def test_fault_schedule_reproducible_in_sim():
    """Same seed -> bit-identical fault-enabled episode results."""
    p = EnvParams(num_bs=3, num_slots=6, max_tasks=4,
                  fault=FaultParams(p_down=0.3, p_up=0.5))
    a = evaluate_scheduler(make_scheduler("round-robin", 3), p, 2, KEY)
    b = evaluate_scheduler(make_scheduler("round-robin", 3), p, 2, KEY)
    assert a["mean_s"] == b["mean_s"]
    assert a["wrong_choice_rate"] == b["wrong_choice_rate"]
    assert a["wrong_choice_rate"] > 0.0    # faults actually fired


def test_failure_aware_scheduler_masks_down_engines():
    p = EnvParams(num_bs=3, num_slots=8, max_tasks=5,
                  fault=FaultParams(p_down=0.3, p_up=0.3, penalty_s=5.0))
    fa = evaluate_scheduler(make_scheduler("failure-aware", 3), p, 2, KEY)
    rr = evaluate_scheduler(make_scheduler("round-robin", 3), p, 2, KEY)
    assert fa["wrong_choice_rate"] == 0.0
    assert rr["wrong_choice_rate"] > 0.0
    assert fa["mean_s"] < rr["mean_s"]


def test_live_observation_appends_availability_and_nan_guards():
    engines, _ = _cluster_pair()
    sched = make_scheduler("failure-aware", 2)
    cluster = EdgeCluster(engines, sched)
    assert sched.layout == ObsLayout(2, fault=True)
    engines[1].fail("test")
    row = np.asarray(cluster.observe(_req(0, _prompt(engines[0]))))
    assert row.shape == (cluster.obs_dim,) == (sched.layout.dim,)
    np.testing.assert_array_equal(row[sched.layout.avail], [1.0, 0.0])
    assert np.isfinite(row).all()


def test_state_dim_mismatch_message_mentions_faults():
    """failure-aware refuses a layout without the availability block, and
    the simulator refuses a scheduler whose availability columns sit
    elsewhere in its rows (here after a QoS block it does not expect)."""
    with pytest.raises(ValueError, match="avail"):
        FailureAwareScheduler(ObsLayout(2, qos=True))
    p = EnvParams(num_bs=2, num_slots=2, max_tasks=2,
                  qos_mix=((INTERACTIVE, 1.0),), fault=FaultParams())
    with pytest.raises(ValueError, match="failure-aware"):
        build_sim_episode(make_scheduler("failure-aware", 2), p)
    # built on the environment's own layout it reads the rows
    build_sim_episode(make_scheduler("failure-aware", 2, qos=True), p)
