"""The paged engine's decode-round inputs: host tables and lengths placed
once a round, the round's tokens kept on the device, and token streams
unchanged against the dense slot path and a recorded sampled run."""
import dataclasses
import functools

import jax
import numpy as np
import pytest

from repro import tracing
from repro.cluster import Request
from repro.configs import get_config, reduced
from repro.models.transformer import init_params
from repro.serving.engine import ServeEngine

CFG = dataclasses.replace(reduced(get_config("qwen2-1.5b")), num_layers=2,
                          dtype="float32")


@functools.cache
def _params():
    return init_params(jax.random.key(0), CFG)


def _prompt(seed, n):
    return np.asarray(jax.random.randint(jax.random.key(seed), (1, n), 0,
                                         CFG.vocab_size), np.int32)


def _paged(**kw):
    return ServeEngine(CFG, _params(), max_len=64, kv_slots=4, paged=True,
                       page_size=8, prefill_chunk=8, **kw)


@pytest.fixture
def spans():
    tracing.start()
    yield tracing.stop
    tracing.stop()


def test_steady_decode_round_places_its_inputs_once(monkeypatch, spans):
    """Sixteen lanes, every one decoding and none prefilling: the round
    moves nothing to the device implicitly (a per-lane slice of the last
    round's tokens would move its index) and places its host arrays with
    one ``device_put``."""
    eng = _paged(max_lanes=16)
    for r in range(16):
        eng.admit(Request(rid=r, prompt=_prompt(r, 8), max_new_tokens=6))
    eng.step()                              # admit, prefill, first round
    assert all(ln is not None and ln.decoding for ln in eng._lanes)
    spans()
    tracing.start()

    real, placed = jax.device_put, []

    def counting(*a, **k):
        placed.append(a[0])
        return real(*a, **k)

    monkeypatch.setattr(jax, "device_put", counting)
    with jax.transfer_guard("disallow"):
        assert eng.dispatch()
    monkeypatch.undo()
    done = eng.collect()
    assert len(placed) == 1 and len(placed[0]) == 2
    got = {s.name: s for s in spans()}
    assert "repro.engine.prefill" not in got
    assert got["repro.engine.decode"].attrs["lanes"] == 16
    assert got["repro.engine.decode_inputs"].attrs == {"fresh": 0,
                                                       "placed": 1}
    assert done == []
    assert all(len(ln.req.tokens) == 3 and all(
        isinstance(t, np.ndarray) for t in ln.req.tokens)
        for ln in eng._lanes)


def _plan():
    """Arrivals by engine step: (rid, prompt, new tokens).  Request 2
    repeats request 0's prompt (a prefix hit that forks its last, partial
    page) and request 3 leaves it mid-page (a copy-on-write fork); seven
    requests over four lanes free and reuse lanes; request 6 ends at its
    first token."""
    a = _prompt(1, 24)
    fork = a.copy()
    fork[0, 20] = (fork[0, 20] + 1) % CFG.vocab_size
    return {0: [(0, a, 6), (1, _prompt(2, 9), 5)],
            3: [(2, a, 5), (3, fork, 4)],
            5: [(4, _prompt(3, 13), 4), (5, _prompt(4, 8), 3),
                (6, _prompt(5, 10), 1)]}


def _staggered(eng):
    plan, reqs = _plan(), []
    for step in range(200):
        for rid, prompt, n in plan.get(step, []):
            reqs.append(Request(rid=rid, prompt=prompt, max_new_tokens=n))
            eng.admit(reqs[-1])
        if step > max(plan) and not eng.has_work:
            break
        eng.step()
    assert all(r.status == "ok" for r in reqs) and eng.kv_leak == 0
    return {r.rid: [int(np.asarray(t).ravel()[0]) for t in r.tokens]
            for r in reqs}, reqs


def test_paged_tokens_match_dense_slot_path(spans):
    """Greedy streams through staggered paged admissions equal the dense
    slot path's, token for token; the run covers a prompt ending while
    other lanes decode, a freed lane reused, a prefix hit and a fork, and
    leaves no device array in any request's tokens."""
    eng = _paged(max_lanes=4)
    paged, reqs = _staggered(eng)
    got = spans()

    dense = ServeEngine(CFG, _params(), max_len=64, kv_slots=4,
                        paged=False)
    want, plan = [], _plan()
    for step in sorted(plan):
        for rid, prompt, n in plan[step]:
            want.append(Request(rid=rid, prompt=prompt, max_new_tokens=n))
            dense.admit(want[-1])
    dense.run_to_completion()
    assert paged == {r.rid: [int(np.asarray(t).ravel()[0])
                             for t in r.tokens] for r in want}

    assert eng.prefix_hits == 2 and eng.cow_forks == 2
    assert all(isinstance(t, np.ndarray) for r in reqs for t in r.tokens)
    rids_by_lane = {}
    for s in got:
        if s.name == "repro.engine.prefill":
            rids_by_lane.setdefault(s.attrs["lane"], set()).add(
                s.attrs["rid"])
    assert max(len(v) for v in rids_by_lane.values()) > 1
    rounds = []
    for k, s in enumerate(got):
        kids = {c.name: c.attrs for c in got if c.parent == k}
        if "repro.engine.decode" in kids:
            rounds.append((kids["repro.engine.decode_inputs"],
                           kids["repro.engine.decode"]))
    assert all(inputs["placed"] == 1 for inputs, _ in rounds)
    assert any(0 < inputs["fresh"] < dec["lanes"] for inputs, dec in rounds)


# recorded from the engine as it was before the round's tokens stayed on
# the device (per-lane inputs and an eager pick of each first token)
_SAMPLED = {0: [256, 234, 246, 249, 310, 329], 1: [158, 112, 274, 148, 69],
            2: [472, 231, 389, 101, 20], 3: [107, 183, 317, 215],
            4: [90, 125, 129, 131], 5: [69, 210, 18], 6: [294]}


def test_sampled_paged_tokens_match_recorded_stream():
    """With ``sample=True`` the first-token draw and the decode rounds take
    the engine's keys in the same order as before, so a seeded run gives
    the same tokens."""
    eng = _paged(max_lanes=4, sample=True)
    eng._rng = jax.random.key(5)
    assert _staggered(eng)[0] == _SAMPLED
