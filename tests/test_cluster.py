"""repro.cluster: Request accounting, continuous batching, and the
Scheduler interface against both the simulator and live engines."""
import dataclasses

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
from repro.core.env import EnvParams
from repro.core.trainer import train_method
from repro.models.transformer import init_params
from repro.serving.engine import ServeEngine

KEY = jax.random.key(0)
ENV = EnvParams(num_bs=2, num_slots=3, max_tasks=3)
ACFG = AgentConfig(train_after=10, replay_capacity=60, batch_size=16,
                   diffusion=DiffusionPolicyConfig(num_steps=2))


def _engine(num_layers=2, kv_slots=2, max_len=40, seed=0, **kw):
    cfg = dataclasses.replace(reduced(get_config("qwen2-1.5b")),
                              num_layers=num_layers)
    params = init_params(jax.random.key(seed), cfg)
    return ServeEngine(cfg, params, max_len=max_len, kv_slots=kv_slots,
                       **kw)


def _prompt(engine, n=1, S=8, seed=0):
    return jax.random.randint(jax.random.key(seed), (n, S), 0,
                              engine.cfg.vocab_size)


# ---------------------------------------------------------------------------
# request-latency accounting
# ---------------------------------------------------------------------------


def test_burst_latency_accounting_monotone_and_sums():
    """A multi-request burst on one engine: per-request timestamps must be
    non-negative, monotone, and decompose the total delay exactly (covers
    the old queue_s/pending_seconds path and continuous batching)."""
    engine = _engine(kv_slots=2)
    prompts = _prompt(engine, 1, 8)
    reqs = [Request(rid=r, prompt=prompts, max_new_tokens=3 + r)
            for r in range(5)]            # burst > kv_slots -> real queueing
    for r in reqs:
        engine.admit(r)
    done = engine.run_to_completion()
    assert len(done) == len(reqs)
    for r in reqs:
        assert r.done
        assert r.t_enqueue <= r.t_prefill_start <= r.t_prefill_end \
            <= r.t_finish
        assert r.queue_s >= 0 and r.prefill_s >= 0 and r.decode_s >= 0
        assert abs((r.queue_s + r.prefill_s + r.decode_s) - r.total_s) \
            < 1e-9
        assert len(r.tokens) == r.max_new_tokens
    # with a 2-slot pool and 5 requests, someone must have queued behind
    # an occupied slot
    assert max(r.queue_s for r in reqs) > 0


def test_continuous_batching_late_request_overtakes():
    """Slot reuse: a late short request joins the decode batch mid-flight
    and finishes before an earlier long request completes."""
    engine = _engine(kv_slots=2)
    prompts = _prompt(engine, 2, 8)
    long = Request(rid=0, prompt=prompts[0:1], max_new_tokens=16)
    engine.admit(long)
    for _ in range(3):
        engine.step()                      # long is mid-decode
    short = Request(rid=1, prompt=prompts[1:2], max_new_tokens=2)
    engine.admit(short)
    engine.run_to_completion()
    assert short.done and long.done
    assert short.t_finish < long.t_finish
    assert short.t_enqueue > long.t_prefill_end   # genuinely late arrival
    assert len(long.tokens) == 16 and len(short.tokens) == 2


def test_slot_reuse_after_free():
    """Freed slots are refilled from the queue; pool stays fixed-size.

    Pinned to the dense slot engine: under the paged pool both requests
    fit in flight at once and the second never waits (that behavior is
    covered in test_paged_kv)."""
    engine = _engine(kv_slots=1, paged=False)
    prompts = _prompt(engine, 1, 8)
    a = Request(rid=0, prompt=prompts, max_new_tokens=2)
    b = Request(rid=1, prompt=prompts, max_new_tokens=2)
    engine.admit(a)
    engine.admit(b)
    engine.run_to_completion()
    assert a.done and b.done
    assert b.t_prefill_start >= a.t_finish - 1e-6   # b waited for the slot
    # identical prompt + greedy decoding -> identical tokens
    np.testing.assert_array_equal(np.stack(a.tokens), np.stack(b.tokens))


def test_pool_decode_matches_sequential_reference():
    """Tokens produced inside the shared slot pool must match a dedicated
    single-request run (per-slot caches are truly independent)."""
    engine = _engine(kv_slots=2)
    prompts = _prompt(engine, 2, 8, seed=3)
    solo = engine.generate(prompts[0:1], 5)
    engine.reset()
    # now serve the same prompt while another request shares the batch
    r0 = Request(rid=0, prompt=prompts[0:1], max_new_tokens=5)
    r1 = Request(rid=1, prompt=prompts[1:2], max_new_tokens=5)
    engine.admit(r0)
    engine.admit(r1)
    engine.run_to_completion()
    np.testing.assert_array_equal(
        np.stack([np.asarray(t[0:1]) for t in solo.tokens]),
        np.stack(r0.tokens))


def test_pending_tokens_tracks_backlog():
    engine = _engine(kv_slots=1)
    prompts = _prompt(engine, 1, 8)
    engine.admit(Request(rid=0, prompt=prompts, max_new_tokens=4))
    engine.admit(Request(rid=1, prompt=prompts, max_new_tokens=6))
    assert engine.pending_tokens == 10
    engine.step()
    assert 0 < engine.pending_tokens < 10
    engine.run_to_completion()
    assert engine.pending_tokens == 0
    assert engine.pending_seconds >= 0.0


# ---------------------------------------------------------------------------
# scheduler interface: same object drives sim and live cluster
# ---------------------------------------------------------------------------


def _schedulers():
    _, states = train_method("lad-ts", ENV, ACFG, episodes=1, key=KEY)
    return {
        "lad-ts": PolicyScheduler("lad-ts", ACFG, states, ENV.layout,
                                  n_max=ENV.max_tasks),
        "jsq": make_scheduler("jsq", 2),
        "round-robin": make_scheduler("round-robin", 2),
        "random": make_scheduler("random", 2),
        "local": make_scheduler("local", 2),
    }


def test_schedulers_drive_simulator_and_live_cluster():
    scheds = _schedulers()
    # --- simulator backend
    for name, s in scheds.items():
        r = evaluate_scheduler(s, ENV, episodes=1, key=jax.random.key(1))
        assert r["count"] > 0, name
        assert r["mean_s"] > 0 and r["p95_s"] >= r["mean_s"] * 0.5, name
    # --- live backend, >= 2 engines, same scheduler objects
    engines = [_engine(num_layers=2, seed=0), _engine(num_layers=4, seed=1)]
    vocab = engines[0].cfg.vocab_size
    for name, s in scheds.items():
        for e in engines:
            e.reset()
        cluster = EdgeCluster(engines, s, seed=2)
        trace = poisson_trace(4, rate=50.0, prompt_len=8, max_new_tokens=3,
                              vocab_size=vocab, num_origins=2, seed=5)
        done = cluster.run(trace)
        stats = summarize(done)
        assert stats["count"] == 4, name
        assert stats["p95_s"] >= stats["mean_s"] > 0, name
        for r in done:
            assert abs((r.queue_s + r.prefill_s + r.decode_s) - r.total_s) \
                < 1e-9


def test_round_robin_cycles_engines():
    s = make_scheduler("round-robin", 3)
    carry = s.init_carry()
    picks = []
    for i in range(6):
        a, carry = s.select_one(carry, jnp.zeros((5,)), 0, 0,
                                jax.random.key(i))
        picks.append(a)
    assert picks == [0, 1, 2, 0, 1, 2]


def test_jsq_picks_emptiest_engine():
    s = make_scheduler("jsq", 3)
    obs = jnp.asarray([1.0, 1.0, 0.9, 0.1, 0.5])   # queues = [.9, .1, .5]
    a, _ = s.select_one(s.init_carry(), obs, 0, 0, jax.random.key(0))
    assert a == 1


def test_local_only_keeps_origin():
    s = make_scheduler("local", 4)
    for origin in range(4):
        a, _ = s.select_one(s.init_carry(), jnp.zeros((6,)), origin, 0,
                            jax.random.key(0))
        assert a == origin


def test_scheduler_select_batch_shapes():
    for name in ("jsq", "round-robin", "random", "local"):
        s = make_scheduler(name, ENV.num_bs)
        a, _ = s.select(s.init_carry(),
                        jnp.zeros((ENV.num_bs, ENV.state_dim)), 0,
                        jax.random.key(0))
        assert a.shape == (ENV.num_bs,)
        assert a.dtype == jnp.int32
        assert bool(((a >= 0) & (a < ENV.num_bs)).all())


def test_unknown_scheduler_raises():
    with pytest.raises(ValueError):
        make_scheduler("nope", 2)
