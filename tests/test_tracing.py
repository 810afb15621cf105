"""repro.tracing: the span recorder, and the spans the cluster, the
scheduler and the engine open at their layer boundaries."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro import tracing
from repro.cluster import (EdgeCluster, PolicyScheduler, Request,
                           make_scheduler)
from repro.configs import get_config, reduced
from repro.core.obs import ObsLayout
from repro.models.transformer import init_params
from repro.serving.engine import ServeEngine


@pytest.fixture(autouse=True)
def _no_recording_left():
    tracing.stop()
    yield
    tracing.stop()


def _by_name(spans, name):
    return [s for s in spans if s.name == name]


def _children(spans, parent):
    i = spans.index(parent)
    return [s for s in spans if s.parent == i]


# ---------------------------------------------------------------------------
# the recorder
# ---------------------------------------------------------------------------


def test_off_records_nothing_and_returns_shared_noop():
    a, b = tracing.span("repro.x"), tracing.span("repro.y", rid=1)
    assert a is b
    with a as sp:
        sp.set(admitted=2)
    assert tracing.stop() == []


def test_nesting_gives_parent_index():
    tracing.start()
    with tracing.span("a"):
        with tracing.span("b"):
            with tracing.span("c"):
                pass
        with tracing.span("d"):
            pass
    with tracing.span("e"):
        pass
    spans = tracing.stop()
    assert [s.name for s in spans] == ["a", "b", "c", "d", "e"]
    assert [s.parent for s in spans] == [None, 0, 1, 0, None]
    for s in spans:
        assert s.end is not None and s.end >= s.start
    assert spans[0].start <= spans[1].start <= spans[2].end <= spans[0].end


def test_rid_and_attrs_are_kept():
    tracing.start()
    with tracing.span("repro.engine.prefill", rid=7, lane=2, start=16,
                      tokens=5):
        pass
    with tracing.span("repro.engine.admission") as sp:
        sp.set(admitted=3, forks=1)
    p, a = tracing.stop()
    assert p.attrs == {"rid": 7, "lane": 2, "start": 16, "tokens": 5}
    assert a.attrs == {"admitted": 3, "forks": 1}


def test_stop_clears():
    tracing.start()
    with tracing.span("a"):
        pass
    assert len(tracing.stop()) == 1
    assert tracing.span("a") is tracing.span("b")      # off again
    assert tracing.stop() == []
    tracing.start()
    assert tracing.stop() == []


def test_a_raise_closes_every_span_it_leaves():
    tracing.start()
    with pytest.raises(ValueError):
        with tracing.span("outer"):
            with tracing.span("inner"):
                raise ValueError("boom")
    with tracing.span("after"):
        pass
    spans = tracing.stop()
    assert all(s.end is not None for s in spans)
    assert spans[2].parent is None


def test_paused_opens_no_spans():
    tracing.start()
    with tracing.paused():
        with tracing.span("hidden"):
            pass
    with tracing.span("seen"):
        pass
    assert [s.name for s in tracing.stop()] == ["seen"]


# ---------------------------------------------------------------------------
# the spans of the serving path
# ---------------------------------------------------------------------------


def _engines(n=2):
    cfg = dataclasses.replace(reduced(get_config("qwen2-1.5b")),
                              num_layers=2)
    return [ServeEngine(cfg, init_params(jax.random.key(i), cfg),
                        max_len=48, kv_slots=2, max_lanes=4,
                        prefill_chunk=8) for i in range(n)]


def _requests(engines, n, plen=12, new=3):
    vocab = engines[0].cfg.vocab_size
    return [Request(rid=r, prompt=jax.random.randint(
        jax.random.key(r), (1, plen), 0, vocab), max_new_tokens=new)
        for r in range(n)]


def _drain(cluster):
    while cluster.busy:
        cluster.step()


def test_cluster_scheduler_and_engine_spans_follow_the_layers():
    engines = _engines()
    cluster = EdgeCluster(engines, make_scheduler("jsq", 2))
    reqs = _requests(engines, 3)
    tracing.start()
    for r in reqs:
        cluster.submit(r)
    _drain(cluster)
    spans = tracing.stop()

    submits = _by_name(spans, "repro.cluster.submit")
    assert [s.attrs["rid"] for s in submits] == [0, 1, 2]
    for s in submits:
        assert s.parent is None
        assert [c.name for c in _children(spans, s)] == [
            "repro.cluster.observe", "repro.scheduler.select",
            "repro.engine.admit"]
    for s in _by_name(spans, "repro.cluster.step"):
        kids = [c.name for c in _children(spans, s)]
        assert kids[0] == "repro.cluster.faults"
        assert set(kids[1:]) <= {"repro.engine.dispatch",
                                 "repro.engine.collect"}
    for d in _by_name(spans, "repro.engine.dispatch"):
        assert spans[d.parent].name == "repro.cluster.step"
        assert d.attrs["engine"] in (0, 1)
        kids = [c.name for c in _children(spans, d)]
        assert kids[0] == "repro.engine.admission"
        assert set(kids) <= {"repro.engine.admission",
                             "repro.engine.prefill",
                             "repro.engine.decode_inputs",
                             "repro.engine.decode"}
    for c in _by_name(spans, "repro.engine.collect"):
        kids = [k.name for k in _children(spans, c)]
        assert kids in ([], ["repro.engine.sync"])
    # 12-token prompts in 8-token chunks: two chunks each, 8 + 4 tokens
    prefill = _by_name(spans, "repro.engine.prefill")
    assert sorted((p.attrs["rid"], p.attrs["start"], p.attrs["tokens"])
                  for p in prefill) == [(r, s, n) for r in range(3)
                                        for s, n in ((0, 8), (8, 4))]
    admitted = sum(a.attrs["admitted"]
                   for a in _by_name(spans, "repro.engine.admission"))
    assert admitted == 3
    # each request decodes twice after its first token; context grows
    decode = _by_name(spans, "repro.engine.decode")
    assert sum(d.attrs["lanes"] for d in decode) == 3 * 2
    for d in decode:
        assert len(d.attrs["lengths"]) == d.attrs["lanes"]
        assert set(d.attrs["lengths"]) <= {13, 14}
    # each round places its host inputs once; each prompt's first token
    # joins the device buffer in the dispatch of its first round
    inputs = _by_name(spans, "repro.engine.decode_inputs")
    assert len(inputs) == len(decode)
    assert all(s.attrs["placed"] == 1 for s in inputs)
    assert sum(s.attrs["fresh"] for s in inputs) == 3
    assert all(r.status == "ok" for r in reqs)


def _fake_policy(num_engines=2):
    """A PolicyScheduler whose compiled select always picks engine 1."""
    s = PolicyScheduler.__new__(PolicyScheduler)
    s.layout, s.n_max, s.greedy = ObsLayout(num_engines), 1, True
    s.states = jnp.zeros((), jnp.int32)
    s._sel1 = jax.jit(lambda carry, row, origin, n, key:
                      (jnp.int32(1), carry))
    return s


def test_policy_sync_is_a_child_of_select_and_warmup_opens_none():
    engines = _engines()
    cluster = EdgeCluster(engines, _fake_policy())
    reqs = _requests(engines, 2)
    for r in reqs:
        r.arrival_s = 0.0
    tracing.start()
    cluster.run(reqs)          # warms select first: no span of its own
    spans = tracing.stop()
    syncs = _by_name(spans, "repro.scheduler.sync")
    assert len(syncs) == len(_by_name(spans, "repro.scheduler.select")) == 2
    for s in syncs:
        assert spans[s.parent].name == "repro.scheduler.select"
    assert {r.engine_id for r in reqs} == {1}


@pytest.mark.parametrize("phase", ["dispatch", "collect"])
def test_quarantine_inside_an_engine_call_closes_its_span(phase):
    engines = _engines()
    cluster = EdgeCluster(engines, make_scheduler("round-robin", 2))
    reqs = _requests(engines, 2)

    def broken():
        raise RuntimeError("device lost")

    setattr(engines[0], {"dispatch": "_dispatch_paged",
                         "collect": "_collect"}[phase], broken)
    tracing.start()
    for r in reqs:
        cluster.submit(r)
    cluster.step()             # engine 0 quarantined inside the call
    _drain(cluster)
    spans = tracing.stop()
    assert cluster.fault_stats["quarantined"] == 1
    assert all(s.end is not None for s in spans)
    failed = [d for d in _by_name(spans, f"repro.engine.{phase}")
              if d.attrs["engine"] == 0]
    assert len(failed) == 1 and _children(spans, failed[0]) == []
    for s in _by_name(spans, "repro.cluster.step"):
        assert s.parent is None
    assert all(r.status == "ok" for r in reqs)
