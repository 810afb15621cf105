"""The traffic generator: deterministic per seed, inside its declared
distributions, the same sizes for every seed, and found by name."""
import json
import pathlib

import numpy as np
import pytest

from bench import harness
from bench.tests import tiny

BENCH = pathlib.Path(harness.__file__).resolve().parent
GEN = harness.generator("sessions")


def _mix(name):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def _plan(mix, seed, seconds=40.0, vocab=151936, origins=2):
    return GEN.generate(mix, seed, seconds, vocab, origins)


@pytest.mark.parametrize("name", ["chat-aligned", "chat-prefix",
                                  "gen-unshared"])
def test_deterministic_per_seed(name):
    mix = _mix(name)
    a, b = _plan(mix, 2**33 + 17), _plan(mix, 2**33 + 17)
    assert len(a.items) == len(b.items) > 0
    for x, y in zip(a.items, b.items):
        assert np.array_equal(x.prompt, y.prompt)
        assert (x.max_new_tokens, x.due_s, x.client, x.origin) == \
            (y.max_new_tokens, y.due_s, y.client, y.origin)


@pytest.mark.parametrize("name", ["chat-aligned", "gen-unshared"])
def test_seeds_share_work_not_tokens(name):
    """Every seed offers the same sizes at the same times, with other
    tokens."""
    mix = _mix(name)
    a, b = _plan(mix, 1), _plan(mix, 2)

    def work(p):
        return [(it.prompt.shape[-1], it.max_new_tokens, it.due_s,
                 it.client, it.origin) for it in p.items]

    assert work(a) == work(b)
    assert any(not np.array_equal(x.prompt[0, :16], y.prompt[0, :16])
               for x, y in zip(a.items, b.items))


def test_chat_within_declared_distributions():
    mix = _mix("chat-aligned")
    p = _plan(mix, 5, seconds=200.0)
    sp = mix["system_prompts"]
    lens = np.asarray([it.prompt.shape[-1] for it in p.items])
    outs = np.asarray([it.max_new_tokens for it in p.items])
    assert lens.max() <= mix["prompt_cap"]
    assert lens.min() >= sp["min"] + mix["user"]["min"]
    assert outs.min() >= mix["output"]["min"]
    assert outs.max() <= mix["output"]["max"]
    # lognormal median 48 (clipped to 8..256): the sample median is near
    assert 38 <= np.median(outs) <= 58
    # open loop: due times inside lead-in + window, sorted
    dues = np.asarray([it.due_s for it in p.items])
    assert dues.min() >= -mix["lead_in_s"] and dues.max() < 200.0
    assert np.all(np.diff(dues) >= 0)
    # request rate near the declared one (sessions end at the cap)
    rate = np.sum(dues >= 0) / 200.0
    assert 0.6 * mix["request_rate_per_s"] < rate \
        <= 1.25 * mix["request_rate_per_s"]
    # turns of one session extend the previous turn's prompt
    by_session = {}
    for it in p.items:
        by_session.setdefault(it.session, []).append(it)
    for turns in by_session.values():
        turns.sort(key=lambda it: it.turn)
        assert len(turns) <= mix["turns"]["max"]
        for x, y in zip(turns, turns[1:]):
            n = x.prompt.shape[-1]
            assert np.array_equal(y.prompt[0, :n], x.prompt[0])


def test_aligned_system_prompts_end_on_page_boundaries():
    """Two sessions behind one system prompt diverge where it ends: at a
    multiple of 16 tokens in the aligned mix, anywhere in the other."""
    def divergences(name):
        p = _plan(_mix(name), 4, seconds=120.0)
        first = [it.prompt[0] for it in p.items if it.turn == 0]
        out = set()
        for i, a in enumerate(first):
            for b in first[i + 1:]:
                n = min(a.size, b.size)
                j = int(np.argmax(a[:n] != b[:n])) if (a[:n] != b[:n]).any() \
                    else n
                if j:
                    out.add(j)
        return out

    aligned = divergences("chat-aligned")
    assert aligned and all(j % 16 == 0 and 256 <= j <= 1024
                           for j in aligned)
    assert any(j % 16 for j in divergences("chat-prefix"))


def test_closed_loop_clients():
    mix = _mix("gen-unshared")
    p = _plan(mix, 9, vocab=49152, origins=1)
    qs = p.client_queues()
    assert len(qs) == mix["clients"]
    assert all(len(q) == mix["sessions_per_client"] for q in qs)
    lens = np.asarray([it.prompt.shape[-1] for it in p.items])
    outs = np.asarray([it.max_new_tokens for it in p.items])
    assert lens.min() >= 64 and lens.max() <= 1024
    assert outs.min() >= 64 and outs.max() <= 1024
    assert 200 <= np.median(outs) <= 320
    # unshared: no two prompts open with the same token
    heads = {int(it.prompt[0, 0]) for it in p.items}
    assert len(heads) == len(p.items)


def test_new_mix_found_by_name(tmp_path):
    """A traffic file added beside the others is found and generated
    without editing any existing file."""
    mix = dict(tiny.TRAFFIC, request_rate_per_s=3.0)
    root = tiny.make_root(tmp_path, traffic=mix, workload="tiny.brand-new")
    cell = harness.resolve(root, "tiny.brand-new")
    assert cell.traffic == mix
    plan = harness.generator(cell.traffic["kind"]).generate(
        cell.traffic, 1, 5.0, 512, 2)
    assert plan.items
    with pytest.raises(harness.Refused):
        harness.resolve(root, "tiny.missing")
