"""Beyond-paper perf features: int8 KV cache, hoisted MoE layout,
weights-stationary serving MoE, dp-even microbatching."""
import dataclasses
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import MoEConfig, get_config, reduced
from repro.models import forward, init_params
from repro.models import moe as moelib


def test_int8_cache_close_to_bf16():
    base = reduced(get_config("qwen2-1.5b"))
    cfg8 = dataclasses.replace(base, kv_cache_dtype="int8")
    key = jax.random.key(1)
    params = init_params(key, base)
    B, S = 2, 16
    toks = jax.random.randint(key, (B, S + 1), 0, base.vocab_size)
    full = forward(params, base, {"tokens": toks},
                   mode="prefill")["last_logits"]
    st = forward(params, cfg8, {"tokens": toks[:, :S]}, mode="prefill",
                 max_len=S + 8)["states"]
    dec = forward(params, cfg8, {"tokens": toks[:, S:S + 1]},
                  mode="decode", states=st)["logits"]
    rel = float(jnp.max(jnp.abs(full - dec))) / float(jnp.abs(full).max())
    assert rel < 0.05, rel


def test_int8_cache_struct():
    from repro.models.attention import init_kv_cache
    cfg = dataclasses.replace(reduced(get_config("musicgen-large")),
                              kv_cache_dtype="int8")
    blk = cfg.layer_pattern()[0]
    c = init_kv_cache(cfg, blk, 2, 32)
    assert c["k"].dtype == jnp.int8
    assert c["k_scale"].shape == (2, cfg.num_kv_heads,
                                  min(32, blk.window or 32), 1)


def test_moe_layout_roundtrip():
    cfg = dataclasses.replace(
        reduced(get_config("mixtral-8x22b")), d_ff=96,
        moe=MoEConfig(num_experts=4, top_k=2))
    M = 8
    w = jax.random.normal(jax.random.key(0), (3, 4, 64, 96))  # stacked
    back = moelib.layout_cols_inv(moelib.layout_cols(w, cfg, M), cfg, M)
    np.testing.assert_array_equal(np.asarray(w), np.asarray(back))
    wd = jax.random.normal(jax.random.key(1), (3, 4, 96, 64))
    back = moelib.layout_rows_inv(moelib.layout_rows(wd, cfg, M), cfg, M)
    np.testing.assert_array_equal(np.asarray(wd), np.asarray(back))


def test_prepare_tree_marks_by_ndim():
    cfg = dataclasses.replace(
        reduced(get_config("dbrx-132b")), d_ff=96,
        moe=MoEConfig(num_experts=4, top_k=2))
    p = moelib.init_moe(jax.random.key(0), cfg, jnp.float32)
    tree = {"layers": {"flat": [{"ffn": p}]}}
    out = moelib.prepare_tree(tree, cfg, M=4)
    assert out["layers"]["flat"][0]["ffn"]["we_up"].ndim == 4
    assert p["we_up"].ndim == 3  # untouched original


_SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses, jax, jax.numpy as jnp
    from repro.configs import get_config, reduced, MoEConfig
    from repro.launch import sharding as shlib
    from repro.launch.mesh import make_production_mesh
    from repro.models import moe as moelib
    cfg = dataclasses.replace(
        reduced(get_config("mixtral-8x22b")), d_ff=96, d_model=64,
        moe=MoEConfig(num_experts=4, top_k=2, capacity_factor=2.0),
        moe_stationary_serve=True, moe_stationary_max_tokens=4096)
    p = moelib.init_moe(jax.random.key(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.key(1), (8, 4, cfg.d_model))
    ref, _, ref_counts = moelib.apply_moe(p, cfg, x)
    worst = 0.0
    for shape in [(2, 4), (4, 2), (8, 1), (1, 8)]:
        mesh = make_production_mesh(shape=shape, axes=("data", "model"))
        ctx = shlib.ShardingContext(mesh)
        with mesh:
            with shlib.use(ctx):
                out, _, counts = jax.jit(
                    lambda p, x: moelib.apply_moe(p, cfg, x))(p, x)
        worst = max(worst, float(jnp.max(jnp.abs(out - ref))))
        # the routing counters come from the shards' own routing
        assert (counts == ref_counts).all(), (shape, counts, ref_counts)
    print("WORST", worst)
    assert worst < 1e-4, worst
""")


@pytest.mark.slow
def test_stationary_moe_matches_local_multidevice():
    import os
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    env = dict(os.environ, PYTHONPATH=src, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _SUBPROC], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr


def test_microbatch_dp_divisibility_logic():
    """B=256, k_cfg=16, dp=32 -> picks k=8 (B/k divides dp)."""
    B, dp, k = 256, 32, 16
    while B % k:
        k -= 1
    while k > 1 and ((B // k) % dp or B % k):
        k -= 1
    assert k == 8
    # single pod dp=16 keeps k=16
    k, dp = 16, 16
    while k > 1 and ((B // k) % dp or B % k):
        k -= 1
    assert k == 16
