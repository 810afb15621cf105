"""The sparse-expert, windowed reference (``reference/moe_swa.py``)
against the program at a tiny Mellum2-shaped size on the CPU: paged
prefill then paged decode agree with the reference's full forward pass
past the window, and a program that ignores the window, runs plain RoPE
on its full layers or drops tokens at an expert's capacity does not.  A
lane that forks a cached page copy-on-write serves what the reference
predicts, on scanned pools of one and of four pattern positions."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.reference import dense_gqa, moe_swa
from bench.tests import tiny

KINDS = ["sliding_attention"] * 3 + ["full_attention"]
YARN = {"rope_type": "yarn", "rope_theta": 10000.0, "factor": 4.0,
        "original_max_position_embeddings": 16, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.1386294361119891}

# d 64, 4 query and 2 KV heads of 16, 8 experts of width 32, top 2,
# window 8, (S, S, S, F) twice, YaRN over 16 original positions
TINY_MELLUM = {
    "name": "tiny-mellum", "arch": "tiny-mellum", "reference": "moe_swa",
    "num_hidden_layers": 8, "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_experts": 8, "num_experts_per_tok": 2,
    "norm_topk_prob": True, "vocab_size": 512, "hidden_act": "silu",
    "rms_norm_eps": 1e-6, "norm_type": "rmsnorm", "rope_theta": 10000.0,
    "sliding_window": 8, "layer_types": KINDS * 2,
    "mlp_layer_types": ["sparse"] * 8,
    "rope_parameters": {"full_attention": YARN,
                        "sliding_attention": {"rope_type": "default",
                                              "rope_theta": 10000.0}},
    "tie_word_embeddings": False, "torch_dtype": "float32",
}
TOL = 2e-4          # float32 program against the float32 reference
FAULT = 1e-2        # what a wrong mechanism moves the logits by, at least


def mellum_config(c: dict, **change):
    """The program's ModelConfig for a Mellum-shaped configuration."""
    from repro.configs.base import ModelConfig, MoEConfig, YarnConfig

    y = c["rope_parameters"]["full_attention"]
    kinds = {"sliding_attention": "sliding", "full_attention": "full"}
    pattern = tuple(("attn", "moe", kinds[k]) for k in c["layer_types"][:4])
    cfg = ModelConfig(
        name=c["name"], family="moe", num_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        norm_eps=c["rms_norm_eps"], rope_theta=c["rope_theta"],
        sliding_window=c["sliding_window"], dtype=c["torch_dtype"],
        tie_embeddings=c["tie_word_embeddings"],
        yarn=YarnConfig(y["factor"], y["original_max_position_embeddings"],
                        y["beta_fast"], y["beta_slow"],
                        y["attention_factor"]),
        moe=MoEConfig(num_experts=c["num_experts"],
                      top_k=c["num_experts_per_tok"],
                      d_expert=c["moe_intermediate_size"]),
        pattern=pattern)
    return dataclasses.replace(cfg, **change)


def paged_logits(cfg, params, toks, plen, chunk=16, page=8):
    """Logits of positions plen-1 .. len(toks)-1: the prompt toks[:plen]
    through paged prefill chunks, then one paged decode step per further
    token, as the engine's compiled steps run them (one lane of two)."""
    from repro.models.transformer import init_paged_states
    from repro.train.steps import (make_paged_decode_step,
                                   make_paged_prefill_step)

    prefill = jax.jit(make_paged_prefill_step(cfg))
    decode = jax.jit(make_paged_decode_step(cfg))
    width = -(-(len(toks) + chunk) // page) + 1
    table = np.zeros(width, np.int32)
    table[:width - 1] = 1 + np.arange(width - 1)[::-1]    # scrambled
    states = init_paged_states(cfg, 2 * width, page)
    padded = np.zeros(-(-plen // chunk) * chunk, np.int32)
    padded[:plen] = toks[:plen]
    for c0 in range(0, plen, chunk):
        logits, states = prefill(
            params, {"tokens": jnp.asarray(padded[None, c0:c0 + chunk]),
                     "start": jnp.int32(c0),
                     "block_table": jnp.asarray(table)}, states)
    out = [np.asarray(logits[0, (plen - 1) % chunk])]
    tables = np.zeros((2, width), np.int32)
    tables[0] = table
    for pos in range(plen, len(toks)):
        lg, _, states, counts = decode(
            params, {"tokens": jnp.asarray([[toks[pos]], [0]]),
                     "block_tables": jnp.asarray(tables),
                     "lengths": jnp.asarray([pos, 0], jnp.int32)},
            states, jax.random.key(0))
        assert counts.shape == (cfg.num_layers, cfg.moe.num_experts)
        assert (counts.sum(-1) == 2 * cfg.moe.top_k).all()   # both lanes
        out.append(np.asarray(lg[0]))
    return np.stack(out)


def _dropping(p, cfg, xf):
    """The capacity-bounded routing the single-device path had: each
    expert keeps its first ceil(1.25 T K / E) tokens and drops the rest."""
    from repro.models import moe as moelib

    w, aux, counts = moelib._route(p["router"], cfg, xf)
    C = moelib._capacity(cfg, xf.shape[0])
    out = jnp.zeros_like(xf)
    for e in range(cfg.moe.num_experts):
        out = out + moelib._one_expert(xf, w[:, e], p["we_up"][e],
                                       p["we_down"][e], p["we_gate"][e], C)
    return out, aux, counts


def _prompt(kind: str) -> np.ndarray:
    rng = np.random.default_rng(5)
    toks = rng.integers(0, 512, 44).astype(np.int32)
    if kind == "skewed":          # identical tokens route identically
        toks[:36] = 7
    return toks


@pytest.mark.parametrize("fault,kind", [
    (None, "random"), (None, "skewed"), ("window", "random"),
    ("rope", "random"), ("capacity", "skewed")])
def test_reference_matches_paged_prefill_then_decode(fault, kind,
                                                     monkeypatch):
    """A 40-token prompt (5 windows) in chunks of 16, then 4 decode
    steps: the program matches the reference, and each wrong mechanism
    is caught.  On the skewed prompt most tokens pick the same experts,
    so a capacity of ceil(1.25 T K / E) drops most of them."""
    from repro.models import moe as moelib
    from repro.models.transformer import init_params

    c = TINY_MELLUM
    cfg = mellum_config(c)
    seed = 13
    params = init_params(jax.random.key(seed), cfg)
    if fault == "window":
        cfg = dataclasses.replace(cfg, sliding_window=None)
    elif fault == "rope":
        cfg = dataclasses.replace(cfg, yarn=None)
    elif fault == "capacity":
        monkeypatch.setattr(moelib, "_apply_moe_local", _dropping)
    toks, plen = _prompt(kind), 40
    got = paged_logits(cfg, params, toks, plen)
    want = moe_swa.logits(c, seed, toks, pad_to=48, start=plen - 1,
                          rows=len(toks) - plen + 1)
    gap = float(np.max(np.abs(got - want)))
    assert gap < TOL if fault is None else gap > FAULT, gap


def test_router_and_expert_weights_drawn_as_the_program_draws_them():
    from repro.models.transformer import init_params

    c = TINY_MELLUM
    cfg = dataclasses.replace(mellum_config(c), scan_layers=False)
    params = init_params(jax.random.key(3), cfg)
    keys = jax.random.split(jax.random.key(3), c["num_hidden_layers"] + 4)
    ref = moe_swa._layer_params(keys[5], (64, 4, 2, 16, 8, 32, jnp.float32))
    got = params["layers"]["flat"][5]
    for name in ("router", "we_up", "we_down", "we_gate"):   # to an ulp
        np.testing.assert_allclose(np.asarray(got["ffn"][name]),
                                   np.asarray(ref[name]), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(got["mixer"]["wq"]),
                               np.asarray(ref["wq"]), rtol=1e-6)


def test_yarn_frequencies_follow_the_published_ramp():
    """Mellum2's full layers: dims below the beta_fast correction keep
    their frequency, dims above beta_slow are divided by the factor."""
    from repro.configs import YarnConfig
    from repro.models.layers import rope_freqs, yarn_freqs

    rp = dict(YARN, rope_theta=500000.0, factor=16.0,
              original_max_position_embeddings=8192,
              attention_factor=1.2772588722239782)
    inv, scale = moe_swa.inv_freq(rp, 128)
    plain = np.asarray(rope_freqs(128, 500000.0))
    assert scale == rp["attention_factor"]
    np.testing.assert_allclose(inv[:18], plain[:18], rtol=1e-6)
    np.testing.assert_allclose(inv[35:], plain[35:] / 16.0, rtol=1e-6)
    got = yarn_freqs(128, 500000.0, YarnConfig(16.0, 8192, 32.0, 1.0,
                                               1.2772588722239782))
    np.testing.assert_allclose(got, inv, rtol=1e-6)


def _served(cfg, params, prompts, new=4, **kw):
    from repro.serving.engine import ServeEngine

    eng = ServeEngine(cfg, params, max_len=96, num_pages=64, max_lanes=2,
                      page_size=16, prefill_chunk=16, prefix_cache=True,
                      **kw)
    out = []
    for p in prompts:
        res = eng.generate(jnp.asarray(p[None]), new)
        out.append(np.concatenate([np.ravel(np.asarray(t))
                                   for t in res.tokens]))
    return eng, out


@pytest.mark.parametrize("shape", ["dense", "mellum"])
def test_forked_lane_serves_what_the_reference_predicts(shape):
    """Prompt B agrees with prompt A on 20 tokens: one cached page and
    4 tokens of the next, which B forks copy-on-write.  With the scanned
    pools' page axis mistaken for the layer axis B read stale KV (gap
    0.359 on the dense shape)."""
    from repro.models.transformer import init_params

    if shape == "dense":
        c = dict(tiny.TINY, torch_dtype="float32")
        cfg, ref = tiny.model_config(c), dense_gqa
    else:
        c, ref = TINY_MELLUM, moe_swa
        cfg = mellum_config(c)
    assert cfg.scan_layers
    seed = 21
    params = init_params(jax.random.key(seed), cfg)
    rng = np.random.default_rng(2)
    a = rng.integers(0, 512, 40).astype(np.int32)
    b = np.concatenate([a[:20], rng.integers(0, 512, 18)]).astype(np.int32)
    eng, (_, served) = _served(cfg, params, [a, b])
    assert eng.cow_forks == 1
    gaps_of = ref.token_gaps if ref is moe_swa else ref.served_gaps
    gaps = gaps_of(c, seed, [(b, served)], 64, 8)["served"][0]
    assert float(gaps.max()) < 1e-3, gaps


def test_engine_counts_tokens_per_expert():
    """Every decode round routes every lane (idle ones too) to top-k
    experts in every layer; the collect span carries the round's mean
    experts reached and its largest load."""
    from repro import tracing
    from repro.models.transformer import init_params

    cfg = mellum_config(TINY_MELLUM)
    params = init_params(jax.random.key(1), cfg)
    tracing.start()
    try:
        eng, _ = _served(cfg, params, [_prompt("random")[:20]], new=5)
    finally:
        spans = tracing.stop()
    L, E, K = cfg.num_layers, cfg.moe.num_experts, cfg.moe.top_k
    assert eng.expert_rounds == 4
    assert eng.expert_tokens.shape == (L, E)
    assert (eng.expert_tokens.sum(-1) == 4 * 2 * K).all()
    assert 1.0 <= eng.experts_hit <= min(E, 2 * K)
    got = [s.attrs for s in spans if s.name == "repro.engine.collect"
           and "experts_hit" in s.attrs]
    assert len(got) == 4
    assert all(1 <= a["expert_load_max"] <= 2 for a in got)
    eng.reset()
    assert eng.expert_tokens is None and eng.experts_hit == 0.0


def test_tiny_mellum_cell_runs_correct(tmp_path, monkeypatch):
    """A whole run of a tiny Mellum-shaped cell through the harness: one
    engine behind JSQ, prompts past the window, the moe_swa check; the
    float8 control, judged by the same checks, is not correct."""
    import time

    from bench import harness
    from repro.serving import builders

    c = dict(TINY_MELLUM, torch_dtype="float32",
             serving=dict(tiny.TINY["serving"], engines=1, max_len=128),
             check=tiny.TINY["check"])
    tiny.patch_program(monkeypatch, c)
    cfg = mellum_config(c)
    monkeypatch.setattr(builders, "get_config", lambda arch: cfg)
    traffic = dict(tiny.TRAFFIC, system_prompts={
        "count": 2, "min": 20, "max": 40, "zipf": 1.0})
    root = tiny.make_root(tmp_path, config=c, traffic=traffic)
    res = harness.run(root, "tiny.mix", 5, 2.0, False, time.perf_counter(),
                      control=True)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["checks"]["compared_tokens"]["value"] >= 20
    assert res["control_correct"] is False
    assert res["control_max_logit_gap"] > c["check"]["max_logit_gap"]
