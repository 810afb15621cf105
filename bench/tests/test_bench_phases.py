"""Readings from the program's own spans (``bench/phases.py``): their
arithmetic, a tiny run with the recorder on, and what they leave alone."""
import json

import numpy as np
import pytest

from bench import harness, phases, trace_reduce
from bench.tests import tiny
from repro.tracing import Span

# ---------------------------------------------------------------------------
# arithmetic on made-up spans: window [100, 110), profiled slice [108, 110)
# ---------------------------------------------------------------------------


def _spans():
    S = Span
    return [
        S("repro.cluster.submit", 99.0, 99.1, None, {"rid": 0}),    # lead-in
        S("repro.scheduler.select", 99.0, 99.05, 0, {}),
        S("repro.cluster.submit", 101.0, 101.010, None, {"rid": 1}),
        S("repro.scheduler.select", 101.0, 101.004, 2, {}),
        S("repro.scheduler.sync", 101.001, 101.004, 3, {}),
        S("repro.cluster.submit", 102.0, 102.010, None, {"rid": 2}),
        S("repro.scheduler.select", 102.0, 102.008, 5, {}),
        S("repro.scheduler.sync", 102.002, 102.008, 6, {}),
        S("repro.cluster.step", 103.0, 103.1, None, {}),
        S("repro.engine.dispatch", 103.0, 103.020, 8, {"engine": 0}),
        S("repro.engine.prefill", 103.001, 103.005, 9,
          {"rid": 1, "lane": 0, "start": 64, "tokens": 64}),
        S("repro.engine.decode", 103.010, 103.012, 9,
          {"lanes": 3, "lengths": [10, 20, 30]}),
        S("repro.engine.dispatch", 104.0, 104.010, 8, {"engine": 1}),
        S("repro.engine.decode", 104.001, 104.002, 12,
          {"lanes": 1, "lengths": [7]}),
        S("repro.engine.dispatch", 107.99, 108.5, None, {"engine": 0}),
        S("repro.scheduler.select", 108.5, 108.6, None, {}),       # slice
        S("repro.engine.dispatch", 109.0, 109.5, None, {"engine": 0}),
        S("repro.engine.decode", 109.1, 109.2, 16,
          {"lanes": 4, "lengths": [1, 2, 3, 4]}),
    ]


def _outside():
    return phases.in_window(_spans(), 100.0, 110.0, (108.0, 110.0))


def test_readings_leave_out_the_lead_in_and_the_profiled_slice():
    sp, keep = _spans(), _outside()
    assert phases.select_ms_p95(sp, keep) == pytest.approx(
        np.percentile([4.0, 8.0], 95))
    assert phases.dispatch_host_ms(sp, keep) == pytest.approx(15.0)
    # 3 + 1 active lanes over two rounds of 4 lanes
    assert phases.lane_occupancy(sp, keep, 4) == pytest.approx(50.0)
    inside = phases.in_window(sp, 100.0, 110.0, (108.0, 110.0), inside=True)
    assert phases.dispatch_host_ms(sp, inside) == pytest.approx(500.0)
    assert phases.lane_occupancy(sp, inside, 4) == pytest.approx(100.0)
    # without a slice every span started in the window counts
    every = phases.in_window(sp, 100.0, 110.0)
    assert phases.dispatch_host_ms(sp, every) == pytest.approx(
        np.mean([20.0, 10.0, 510.0, 500.0]))


def test_sync_share_and_self_time():
    sp, keep = _spans(), _outside()
    assert phases.select_sync_share(sp, keep) == pytest.approx(
        100 * (3.0 + 6.0) / (4.0 + 8.0))
    got = phases.phases(sp, keep)
    assert got["repro.engine.dispatch"]["count"] == 2
    # dispatch 0: 20 ms less 4 of prefill and 2 of decode; dispatch 1:
    # 10 less 1
    assert got["repro.engine.dispatch"]["self_ms"] == pytest.approx(
        (14.0 + 9.0) / 2)
    assert got["repro.cluster.submit"]["mean_ms"] == pytest.approx(10.0)


def test_admission_and_slowest_read_the_attributes():
    sp = [Span("repro.engine.admission", 1.0, 1.004, None,
               {"admitted": 2, "forks": 1}),
          Span("repro.engine.admission", 2.0, 2.002, None,
               {"admitted": 0, "forks": 0}),
          Span("repro.engine.prefill", 2.0, 2.003, None,
               {"rid": 4, "lane": 1, "start": 0, "tokens": 64}),
          Span("repro.engine.prefill", 3.0, 3.001, None,
               {"rid": 5, "lane": 2, "start": 64, "tokens": 9})]
    keep = phases.in_window(sp, 0.0, 10.0)
    got = phases.admission(sp, keep)
    assert (got["admitted"], got["forks"]) == (2, 1)
    assert got["ms_per_admitted"] == pytest.approx(3.0)
    top = phases.slowest(sp, keep, "repro.engine.prefill", k=1)
    assert top == [[pytest.approx(3.0), {"rid": 4, "lane": 1, "start": 0,
                                         "tokens": 64}]]
    assert phases.admission([], [])["ms_per_admitted"] is None


def test_readings_of_nothing_are_none():
    assert phases.select_ms_p95([], []) is None
    assert phases.select_sync_share([], []) is None
    assert phases.dispatch_host_ms([], []) is None
    assert phases.lane_occupancy([], [], 4) is None


def test_dispatch_work_from_span_attributes():
    work = phases.dispatch_work(_spans())
    assert [(w["engine"], w["prefill"], w["decode"]) for w in work] == [
        (0, [(64, 64)], [10, 20, 30]), (1, [], [7]), (0, [], []),
        (0, [], [1, 2, 3, 4])]


# ---------------------------------------------------------------------------
# tiny runs on the CPU with the recorder on
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One traced tiny run: result, record, spans, and the repro.* host
    events of its profiler trace."""
    mp = pytest.MonkeyPatch()
    try:
        tiny.patch_program(mp)
        root = tiny.make_root(tmp_path_factory.mktemp("phases"),
                              traffic=dict(tiny.TRAFFIC, trace_s=1.0))
        res, rec, spans = phases.run_one(root, "tiny.mix", 5, 2.0, True,
                                         True)
        xplane = trace_reduce.find_xplane(
            str(root / "bench_out" / "trace" / "tiny.mix"))
        yield root, res, rec, spans, phases.program_events(xplane)
    finally:
        mp.undo()


def test_traced_run_holds_program_spans(traced):
    root, res, rec, spans, events = traced
    assert res["correct"], res["checks"]
    names = {e.name for e in events}
    assert {"repro.cluster.submit", "repro.scheduler.select",
            "repro.cluster.step", "repro.engine.dispatch",
            "repro.engine.decode", "repro.engine.collect"} <= names
    out = phases.summary(res, rec, spans, root)
    for k in ("select_ms_p95", "select_sync_share", "dispatch_host_ms",
              "lane_occupancy", "dispatch_host_ms_in_slice"):
        assert out[k] is not None, k
    assert out["select_sync_share"] == 0.0      # JSQ: no sync span
    assert 0 < out["lane_occupancy"] <= 100
    assert out["admission"]["admitted"] > 0
    assert out["admission"]["forks"] == 0          # page-aligned prompts
    assert {"rid", "lane", "start", "tokens"} <= set(
        out["slowest"]["repro.engine.prefill"][0][1])
    assert "repro.engine.dispatch" in out["phases_in_slice"]
    json.dumps(out)
    # the harness's own result line is untouched by the recording
    assert set(res["metrics"]) <= {m["name"] for m in
                                   json.loads((root / "BENCHMARK.json")
                                              .read_text())["per_layer"]}


def test_idle_gap_is_named_by_the_engine_phase(traced):
    """No device planes on the CPU: a made-up chip, busy all through the
    slice but for a moment inside the first decode enqueue (or prefill
    chunk) the profiler saw."""
    _, _, rec, _, events = traced
    lo, hi = rec.trace.window
    dec = next(e for e in events if lo < e.start and e.end < hi
               and e.name in ("repro.engine.decode", "repro.engine.prefill"))
    mid = 0.5 * (dec.start + dec.end)
    a, b = mid - 1e-6, mid + 1e-6
    chip = trace_reduce.Chip(0, ops=[trace_reduce.Event("op", lo, a),
                                     trace_reduce.Event("op", b, hi)],
                             modules=[])
    t = trace_reduce.Trace([chip], rec.trace.spans, rec.trace.window)
    got = phases.named_gaps(t, events)
    name, s = got["idle_gaps"][0]
    assert name.startswith(dec.name + " at +")
    assert s == pytest.approx(b - a)
    # with the harness's spans alone the same gap has a coarser name
    plain = trace_reduce.idle_gaps(t)[0][0]
    assert plain.startswith(("bench.", "host.other"))


def _existing_readers():
    bench = json.loads((tiny.REPO / "BENCHMARK.json").read_text())
    return [m["name"] for m in bench["per_layer"]]


def test_existing_readers_ignore_program_spans(traced):
    """Every per-layer reader of the benchmark reads the same number
    whether the trace also holds the program's repro.* spans or not."""
    _, _, rec, _, events = traced
    before = {n: harness.reader(n).read(rec) for n in _existing_readers()}
    plain = rec.trace
    rec.trace = trace_reduce.from_parts(plain.chips, plain.spans + events)
    try:
        after = {n: harness.reader(n).read(rec)
                 for n in _existing_readers()}
    finally:
        rec.trace = plain
    assert after == before
    assert before["submit_ms_p95"] is not None


def test_span_attributes_give_the_harness_dispatch_record(tmp_path,
                                                          monkeypatch):
    """The prefill chunks and decode context lengths that the harness
    rebuilds from the engine's internals, dispatch by dispatch, equal
    those the program's spans carry.  The slice covers the whole run;
    outputs of at least three tokens keep every lane alive through the
    dispatch of its last prompt chunk, which the harness's record needs
    (it reads the lanes left after the dispatch)."""
    tiny.patch_program(monkeypatch)
    traffic = dict(tiny.TRAFFIC, trace_s=10.0,
                   output=dict(tiny.TRAFFIC["output"], min=3))
    root = tiny.make_root(tmp_path, traffic=traffic)
    res, rec, spans = phases.run_one(root, "tiny.mix", 6, 2.0, True, True)
    lo, hi = rec.trace_span
    from_spans = [(d["engine"], d["prefill"], d["decode"])
                  for d in phases.dispatch_work(spans)
                  if lo <= d["t"] < hi and (d["prefill"] or d["decode"])]
    from_harness = [(d["engine"], d["prefill"], d["decode"])
                    for d in rec.dispatches
                    if d["prefill"] or d["decode"]]
    assert len(from_harness) > 10
    assert sum(len(p) for _, p, _ in from_harness) > 5
    assert from_spans == from_harness


def test_recorder_off_records_nothing(tmp_path, monkeypatch):
    tiny.patch_program(monkeypatch)
    root = tiny.make_root(tmp_path)
    res, rec, spans = phases.run_one(root, "tiny.mix", 7, 1.0, False, False)
    assert spans == [] and res["correct"]
    out = phases.summary(res, rec, spans, root)
    assert out["dispatch_host_ms"] is None and out["spans"] == 0
    assert set(out["metrics"]) == {"ttft_p95_ms", "tpot_p95_ms",
                                   "delay_p95_ms", "tokens_per_s", "setup_s"}
