"""The arithmetic of the end-to-end and per-layer metrics, the trace
reduction, and the operation and byte counts behind the shares."""
import math
import types

import numpy as np
import pytest

from bench import flops, harness, peaks, readers, trace_reduce
from bench.tests import tiny


def _req(rid, due, first, finish, tokens, status="ok", prompt=100,
         prefix=0):
    r = types.SimpleNamespace(
        rid=rid, t_arrival=due, t_enqueue=due + 0.001,
        t_prefill_start=due + 0.002, t_prefill_end=first, t_finish=finish,
        tokens=[np.zeros(1, np.int32)] * tokens, status=status,
        prompt=np.zeros((1, prompt), np.int32), prefix_tokens=prefix,
        max_new_tokens=tokens)
    return r


def _record(reqs, loop="open", seconds=10.0, **kw):
    cell = harness.Cell("tiny.mix", 1, tiny.TINY, dict(tiny.TRAFFIC,
                                                       loop=loop), [], [])
    rec = harness.RunRecord(cell=cell, seed=0, seconds=seconds, setup_s=1.5,
                            chips=1, peaks=peaks.peaks("TPU v5 lite"),
                            t_open=100.0, t_close=100.0 + seconds,
                            requests=reqs,
                            due={r.rid: r.t_arrival for r in reqs}, **kw)
    return rec


def test_p95_over_every_request_due_in_window():
    # 100 requests due in the window with TTFT 1..100 ms, plus lead-in
    # and after-window requests that must not count
    reqs = [_req(i, 100.0 + 0.05 * i, 100.0 + 0.05 * i + (i + 1) / 1e3,
                 101.0 + 0.05 * i, 5) for i in range(100)]
    reqs.append(_req(900, 99.0, 99.0 + 5.0, 105.0, 5))      # lead-in
    rec = _record(reqs)
    got = harness.reader("ttft_p95_ms").read(rec)
    assert got == pytest.approx(np.percentile(np.arange(1, 101), 95))
    assert harness.reader("delay_p95_ms").read(rec) == pytest.approx(
        np.percentile([(101.0 - 100.0) * 1e3] * 100, 95))


def test_tpot_uses_tokens_after_the_first():
    reqs = [_req(0, 100.0, 100.5, 101.5, 11)]        # 1 s over 10 tokens
    assert harness.reader("tpot_p95_ms").read(_record(reqs)) == \
        pytest.approx(100.0)


def test_failures_are_counted_not_timed():
    reqs = [_req(0, 100.0, 100.1, 100.5, 4),
            _req(1, 101.0, None, None, 4, status="pending"),
            _req(2, 102.0, None, None, 4, status="failed")]
    rec = _record(reqs)
    assert harness._failed(rec) == 2
    assert len(harness._attempted(rec)) == 3
    assert harness.reader("ttft_p95_ms").read(rec) == pytest.approx(100.0)


def test_tokens_per_s_over_the_whole_window():
    rec = _record([], loop="closed", window_tokens=4000,
                  window_tokens_s=10.0)
    assert harness.reader("tokens_per_s").read(rec) == 400.0
    assert harness.reader("tokens_per_s").read(_record([])) is None


def test_prefix_token_share():
    reqs = [_req(0, 100.0, 100.1, 100.5, 4, prompt=400, prefix=256),
            _req(1, 101.0, 101.1, 101.5, 4, prompt=100, prefix=0)]
    assert harness.reader("prefix_token_share").read(_record(reqs)) == \
        pytest.approx(100.0 * 256 / 500)


def test_readers_return_nothing_without_a_trace():
    rec = _record([_req(0, 100.0, 100.1, 100.5, 4)])
    for name in ("prefill_chunk_ms", "decode_step_ms", "idle_share.ttft",
                 "paged_decode_roofline.tpot", "step_mfu.tpot",
                 "collective_share", "cluster_step_ms"):
        assert harness.reader(name).read(rec) is None, name


def test_unknown_device_has_no_peaks():
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("cpu")


# ---------------------------------------------------------------------------
# trace reduction
# ---------------------------------------------------------------------------


def _ev(name, a, b):
    return trace_reduce.Event(name, a, b)


def _trace():
    chip0 = trace_reduce.Chip(0, ops=[
        _ev("fusion.1", 1.0, 2.0), _ev("fusion.2", 1.5, 2.5),   # overlap
        _ev("%paged_flash_decode.3 = bf16[32,6,128] custom-call(...)", 3.0,
            3.5),
        _ev("%all-reduce.4 = bf16[16,4608] all-reduce(...)", 3.5, 3.75),
        _ev("fusion.9", 9.5, 12.0)],                            # clipped
        modules=[_ev("jit_paged_decode_step(7)", 3.0, 4.0),
                 _ev("jit_paged_prefill_step(8)", 1.0, 2.5)])
    chip1 = trace_reduce.Chip(1, ops=[_ev("fusion.1", 2.0, 4.0)],
                              modules=[_ev("jit_paged_decode_step(7)", 2.0,
                                           4.0)])
    spans = [_ev("bench.slice", 0.0, 10.0), _ev("bench.step", 4.0, 9.0),
             _ev("bench.collect.e0", 4.5, 8.0)]
    return trace_reduce.from_parts([chip0, chip1], spans)


def test_idle_share_is_a_union_of_intervals():
    t = _trace()
    # chip 0 busy: [1, 2.5] + [3, 3.75] + [9.5, 10] = 2.75 s; chip 1: 2 s
    assert trace_reduce.busy_s(t) == pytest.approx((2.75 + 2.0) / 2)
    rec = _record([], trace=t)
    assert readers.idle_share(rec) == pytest.approx(
        100 * (1 - 2.375 / 10.0))


def test_idle_gaps_named_by_host_span():
    gaps = trace_reduce.idle_gaps(_trace())
    name, s = gaps[0]
    assert s == pytest.approx(9.5 - 3.75)
    assert name.startswith("bench.collect.e0")
    assert sum(g[1] for g in gaps) == pytest.approx(10.0 - 2.75)


def test_module_and_kernel_times():
    t = _trace()
    mean, n = trace_reduce.per_call_s(t, "modules", "^jit_paged_decode_step")
    assert (mean, n) == (pytest.approx(1.5), 1)
    mean, _ = trace_reduce.per_call_s(t, "ops", readers.NAMES["decode_kernel"])
    assert mean == pytest.approx(0.5)
    rec = _record([], trace=t)
    # chip 0: 0.25 s of all-reduce in a 1 s decode run; chip 1: none
    assert harness.reader("collective_share").read(rec) == pytest.approx(
        100 * (0.25 + 0.0) / 2)


def test_recorded_cpu_trace_is_read(tmp_path):
    """A real profiler trace: the host spans and the slice come back."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.slice"):
        with jax.profiler.TraceAnnotation("bench.step"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    t = trace_reduce.load(trace_reduce.find_xplane(str(tmp_path)))
    assert t.window_s > 0
    assert any(s.name == "bench.step" for s in t.spans)
    assert t.chips == []            # no TPU planes on the CPU


# ---------------------------------------------------------------------------
# counts behind the shares
# ---------------------------------------------------------------------------

QWEN = {"num_hidden_layers": 28, "hidden_size": 1536,
        "num_attention_heads": 12, "num_key_value_heads": 2, "head_dim": 128,
        "intermediate_size": 8960, "vocab_size": 151936,
        "hidden_act": "silu", "torch_dtype": "bfloat16"}


def test_decode_bytes_count_live_context_only():
    f, b = flops.decode_attention(QWEN, [100, 1000])
    keys = 1100
    assert b == keys * 2 * 128 * 2 * 2 + 2 * 12 * 128 * 2 * 2
    assert f == 4 * 12 * 128 * keys
    # padding pages or idle lanes add nothing
    assert flops.decode_attention(QWEN, []) == (0, 0)


def test_flops_per_token():
    per_layer = (1536 * 12 * 128 + 2 * 1536 * 2 * 128 + 12 * 128 * 1536
                 + 3 * 1536 * 8960)
    params = 28 * per_layer + 1536 * 151936
    assert flops.matmul_params(QWEN) == params
    assert flops.token_flops(QWEN, 10) == 2 * params + 4 * 28 * 12 * 128 * 10
    # a prefill of n positions from start is n tokens over growing context
    assert flops.prefill_flops(QWEN, 64, 3) == sum(
        flops.token_flops(QWEN, 64 + k + 1) for k in range(3))


def test_roofline_and_mfu_from_recorded_dispatches():
    t = _trace()
    c = dict(tiny.TINY)
    rec = _record([], trace=t, trace_span=(50.0, 60.0), dispatches=[
        {"t": 51.0, "engine": 0, "prefill": [(0, 16)], "decode": [20, 30]},
        {"t": 70.0, "engine": 0, "prefill": [], "decode": [5]}])  # outside
    rec.cell.config = c
    f, b = flops.decode_attention(c, [20, 30])
    need = max(f / 197e12, b / 819e9)
    assert readers.decode_roofline(rec) == pytest.approx(100 * need / 0.5)
    total = flops.prefill_flops(c, 0, 16) + flops.token_flops(c, 20) + \
        flops.token_flops(c, 30)
    assert readers.step_mfu(rec) == pytest.approx(
        100 * total / (10.0 * 197e12))
    assert not math.isnan(readers.step_mfu(rec))
