"""Whole runs at a tiny size on the CPU: the comparison with the plain
reference passes on a sound run and fails on the float8 control and on
a served token altered where it is produced; the command refuses to
run without a TPU."""
import json
import os
import pathlib
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench.reference import dense_gqa
from bench.tests import tiny

REPO = pathlib.Path(__file__).resolve().parents[2]


@pytest.fixture
def root(tmp_path, monkeypatch):
    tiny.patch_program(monkeypatch)
    return tiny.make_root(tmp_path)


def _run(root, seed, **kw):
    return harness.run(root, "tiny.mix", seed, 2.0, False,
                       time.perf_counter(), **kw)


def test_sound_run_is_correct_and_control_is_not(root):
    """Program readings at this size stay under 0.01 and float8 control
    readings over 0.04 (seeds 1-6, CPU); the tiny limit lies between."""
    res = _run(root, 3, control=True)
    chk = res["checks"]
    assert res["correct"], chk
    assert res["failed"] == 0 and res["attempted"] > 0
    assert chk["compared_tokens"]["value"] >= 20
    assert chk["max_logit_gap"]["value"] <= tiny.TINY["check"][
        "max_logit_gap"]
    assert res["control_max_logit_gap"] > tiny.TINY["check"]["max_logit_gap"]
    assert res["control_correct"] is False
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"ttft_p95_ms", "tpot_p95_ms",
                                   "delay_p95_ms", "tokens_per_s", "setup_s"}


def test_token_altered_where_produced_is_caught(root, monkeypatch):
    """Every decode round's tokens shifted by one inside the compiled
    step's caller: the run must read not correct."""
    from repro.serving import compiled

    real = compiled.paged_decode_step

    def broken(cfg, *a, **k):
        step = real(cfg, *a, **k)

        def wrapped(*args, **kw):
            logits, tok, states = step(*args, **kw)
            return logits, (tok + 1) % cfg.vocab_size, states
        return wrapped

    monkeypatch.setattr(compiled, "paged_decode_step", broken)
    res = _run(root, 3)
    assert not res["correct"]
    assert res["checks"]["max_logit_gap"]["value"] > \
        tiny.TINY["check"]["max_logit_gap"]


def test_decode_state_left_unchanged_is_caught(root, monkeypatch):
    """The decode round hands back the page pools it was given, so the
    KV of every decoded token is lost: the run must read not correct."""
    from repro.serving import compiled

    real = compiled.paged_decode_step

    def broken(cfg, *a, **k):
        step = real(cfg, *a, **k)

        def wrapped(params, batch, states, *rest, **kw):
            kept = jax.tree.map(jnp.copy, states)
            logits, tok, _ = step(params, batch, states, *rest, **kw)
            return logits, tok, kept
        return wrapped

    monkeypatch.setattr(compiled, "paged_decode_step", broken)
    res = _run(root, 3)
    assert not res["correct"]
    assert res["checks"]["max_logit_gap"]["value"] > \
        tiny.TINY["check"]["max_logit_gap"]


def test_traced_run_reports_per_layer_metrics(root):
    res = harness.run(root, "tiny.mix", 4, 2.0, True, time.perf_counter())
    assert res["correct"], res["checks"]
    assert "breakdown" in res
    assert res["device"]["window_s"] > 0
    # host-side per-layer metrics exist; device ones need a TPU trace
    assert {"submit_ms_p95", "queue_wait_ms_p95",
            "prefix_token_share"} <= set(res["metrics"])
    assert "prefill_chunk_ms" not in res["metrics"]


def test_reference_matches_engine_prefill_then_decode():
    """The plain reference against the engine's own compiled paged
    prefill (two chunks) and first paged decode step, float32."""
    from repro.serving import compiled
    from repro.serving.engine import ServeEngine

    c = dict(tiny.TINY, torch_dtype="float32")
    cfg = tiny.model_config(c)
    seed = 11
    eng = ServeEngine(cfg, compiled.param_init(cfg)(jax.random.key(seed)),
                      max_len=128, num_pages=32, max_lanes=2,
                      prefill_chunk=16)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, c["vocab_size"], 33).astype(np.int32)
    plen = 32
    table = np.zeros(eng._row_width, np.int32)
    table[:3] = [1, 2, 3]
    eng._ensure_paged_states()
    states = eng._paged_states
    for c0 in (0, 16):
        logits, states = eng._paged_prefill(
            eng.params, {"tokens": jnp.asarray(toks[None, c0:c0 + 16]),
                         "start": jnp.int32(c0),
                         "block_table": jnp.asarray(table)}, states)
    prefill_last = np.asarray(logits[0, -1])
    tables = np.zeros((2, eng._row_width), np.int32)
    tables[0] = table
    dec, _, _ = eng._paged_decode(
        eng.params, {"tokens": jnp.asarray([[toks[plen]], [0]]),
                     "block_tables": jnp.asarray(tables),
                     "lengths": jnp.asarray([plen, 0], jnp.int32)},
        states, jax.random.key(0))
    want = dense_gqa.logits(c, seed, toks, pad_to=48, start=plen - 1,
                            rows=2)
    np.testing.assert_allclose(prefill_last, want[0], atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(dec[0]), want[1], atol=1e-5,
                               rtol=1e-5)


def test_no_tpu_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(REPO / "bench" / "run.py"), "--workload",
         "qwen2-1.5b-x2.chat-aligned", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=REPO, timeout=240)
    assert p.returncode != 0
    for line in p.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)
    assert "needs a TPU" in p.stderr
