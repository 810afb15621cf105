"""MoE: local reference semantics + sharded-path equivalence (the
multi-device check runs in a subprocess so the main test session keeps the
single real CPU device)."""
import dataclasses
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import MoEConfig, get_config, reduced
from repro.models import moe as moelib

KEY = jax.random.key(0)


def _cfg(E=4, K=2, cf=2.0, d=64, f=96):
    base = reduced(get_config("dbrx-132b"))
    return dataclasses.replace(
        base, d_model=d, d_ff=f, head_dim=d // base.num_heads,
        moe=MoEConfig(num_experts=E, top_k=K, capacity_factor=cf))


def test_output_shape_and_aux():
    cfg = _cfg()
    p = moelib.init_moe(KEY, cfg, jnp.float32)
    x = jax.random.normal(KEY, (2, 16, cfg.d_model))
    out, aux, _ = moelib.apply_moe(p, cfg, x)
    assert out.shape == x.shape
    assert float(aux) > 0  # load-balance loss is positive


def test_topk_only_active_experts_contribute():
    """Zeroing the weights of all experts outside a token's top-k must not
    change that token's output."""
    cfg = _cfg(E=4, K=1, cf=4.0)
    p = moelib.init_moe(KEY, cfg, jnp.float32)
    x = jax.random.normal(jax.random.key(1), (1, 8, cfg.d_model))
    out1, *_ = moelib.apply_moe(p, cfg, x)
    # find each token's chosen expert, then zero a never-chosen expert
    xf = x.reshape(-1, cfg.d_model)
    probs = jax.nn.softmax(xf @ p["router"], axis=-1)
    chosen = set(np.asarray(jnp.argmax(probs, -1)).tolist())
    unused = [e for e in range(4) if e not in chosen]
    if not unused:
        pytest.skip("all experts used by this sample")
    e = unused[0]
    p2 = jax.tree_util.tree_map(lambda a: a, p)
    for k in ("we_up", "we_down", "we_gate"):
        if k in p2:
            p2[k] = p2[k].at[e].set(0.0)
    out2, *_ = moelib.apply_moe(p2, cfg, x)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                               atol=1e-6)


def _mixture(p, cfg, x):
    """The exact top-k mixture, token by token and expert by expert."""
    xf = x.reshape(-1, cfg.d_model)
    gate, idx = jax.lax.top_k(jax.nn.softmax(xf @ p["router"], -1),
                              cfg.moe.top_k)
    gate = gate / gate.sum(-1, keepdims=True)
    out = []
    for t in range(xf.shape[0]):
        y = 0.0
        for g, e in zip(gate[t], idx[t]):
            h = (jax.nn.silu(xf[t] @ p["we_gate"][e])
                 * (xf[t] @ p["we_up"][e]))
            y = y + g * (h @ p["we_down"][e])
        out.append(y)
    return jnp.stack(out).reshape(x.shape)


def test_capacity_drops_tokens():
    """The single-device path drops no token, whatever the capacity
    factor: at ~0 (a capacity of one token per expert) it still gives
    every token its exact top-k mixture, and counts every routed token."""
    cfg = _cfg(E=4, K=2, cf=1e-6)
    p = moelib.init_moe(KEY, cfg, jnp.float32)
    x = jax.random.normal(KEY, (2, 32, cfg.d_model))
    out, _, counts = moelib.apply_moe(p, cfg, x)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_mixture(p, cfg, x)),
                               atol=1e-5, rtol=1e-5)
    assert int(counts.sum()) == 2 * 32 * 2 and counts.shape == (4,)


@pytest.mark.parametrize("skew", [False, True])
def test_dropless_when_most_tokens_choose_one_expert(skew):
    """64 experts, top 8, 16 decode rows: a capacity bound of
    ceil(1.25 T K / E) = 3 tokens per expert would drop tokens in most
    rounds, and every one on the skewed batch (identical rows)."""
    cfg = _cfg(E=64, K=8, cf=1.25, f=32)
    p = moelib.init_moe(KEY, cfg, jnp.float32)
    x = jax.random.normal(jax.random.key(2), (16, 1, cfg.d_model))
    if skew:
        x = jnp.broadcast_to(x[:1], x.shape)
    out, _, counts = moelib.apply_moe(p, cfg, x)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_mixture(p, cfg, x)),
                               atol=1e-5, rtol=1e-5)
    if skew:
        assert int(counts.max()) == 16
    assert int(counts.sum()) == 16 * 8


def test_moe_gradients_flow():
    cfg = _cfg()
    p = moelib.init_moe(KEY, cfg, jnp.float32)
    x = jax.random.normal(KEY, (1, 16, cfg.d_model))

    def loss(p):
        out, aux, _ = moelib.apply_moe(p, cfg, x)
        return (out ** 2).mean() + aux

    g = jax.grad(loss)(p)
    for name in ("we_up", "we_down", "router"):
        assert float(jnp.abs(g[name]).max()) > 0, name


_SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses, jax, jax.numpy as jnp
    from repro.configs import get_config, reduced, MoEConfig
    from repro.launch import sharding as shlib
    from repro.launch.mesh import make_production_mesh
    from repro.models import moe as moelib
    cfg = dataclasses.replace(
        reduced(get_config("dbrx-132b")), d_ff=96,
        moe=MoEConfig(num_experts=4, top_k=2, capacity_factor=2.0))
    p = moelib.init_moe(jax.random.key(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.key(1), (2, 32, cfg.d_model))
    ref, _, ref_counts = moelib.apply_moe(p, cfg, x)
    worst = 0.0
    for shape in [(2, 4), (1, 8), (4, 2), (8, 1)]:
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
def test_sharded_path_matches_local_multidevice():
    import os
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    env = dict(os.environ, PYTHONPATH=src, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _SUBPROC], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "WORST" in r.stdout
