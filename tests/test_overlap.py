"""Fleet-scale serving fast path: overlapped dispatch/collect parity,
the shared compiled-step cache, and sharded big-model engines."""
import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.cluster import (EdgeCluster, Request, make_scheduler,
                           poisson_trace)
from repro.configs import get_config, reduced
from repro.launch.mesh import make_production_mesh, make_smoke_mesh
from repro.models.transformer import init_params
from repro.serving import compiled
from repro.serving.builders import build_fleet, build_sharded_engine
from repro.serving.engine import ServeEngine


def _engine(arch="qwen2-1.5b", num_layers=2, kv_slots=2, max_len=40,
            seed=0, **kw):
    cfg = dataclasses.replace(reduced(get_config(arch)),
                              num_layers=num_layers)
    params = init_params(jax.random.key(seed), cfg)
    return ServeEngine(cfg, params, max_len=max_len, kv_slots=kv_slots,
                       **kw)


def _mixed_fleet(seed0=3):
    """Paged (attention) + dense (recurrent) engines behind one cluster."""
    return build_fleet(["qwen2-1.5b", "xlstm-350m", "starcoder2-3b"],
                       max_len=48, depths=[2, 2, 2], seed0=seed0,
                       kv_slots=2, prefill_chunk=8, max_lanes=4)


def _drain(cluster, n):
    done = []
    for _ in range(10_000):
        if len(done) >= n and not cluster.busy:
            break
        done += cluster.step()
    return done


def _run_trace(overlap, seed0=3):
    """Submit an identical burst into an identical fresh fleet and drain."""
    engines = _mixed_fleet(seed0)
    cluster = EdgeCluster(engines, make_scheduler("jsq", len(engines)),
                          seed=11, overlap=overlap)
    trace = poisson_trace(8, rate=1e9, prompt_len=10, max_new_tokens=5,
                          vocab_size=min(e.cfg.vocab_size for e in engines),
                          num_origins=len(engines), seed=5)
    for r in trace:
        cluster.submit(r)
    done = _drain(cluster, len(trace))
    return engines, {r.rid: r for r in done}


# ---------------------------------------------------------------------------
# overlapped dispatch/collect parity vs serial stepping
# ---------------------------------------------------------------------------


def test_overlap_parity_with_serial_stepping():
    """Same burst through overlap=False and overlap=True clusters over a
    mixed paged+dense fleet: tokens bit-identical, same terminal statuses,
    ordered timestamps, and no leaked KV reservations."""
    eng_serial, serial = _run_trace(overlap=False)
    eng_overlap, overlap = _run_trace(overlap=True)
    assert serial.keys() == overlap.keys() and len(serial) == 8
    for rid, a in serial.items():
        b = overlap[rid]
        assert a.status == b.status == "ok"
        ta = np.asarray([np.asarray(t) for t in a.tokens])
        tb = np.asarray([np.asarray(t) for t in b.tokens])
        assert np.array_equal(ta, tb), f"rid {rid}: token divergence"
        assert b.t_enqueue <= b.t_prefill_start <= b.t_prefill_end \
            <= b.t_finish
    for e in eng_serial + eng_overlap:
        assert e.kv_leak == 0
        assert not e.has_work


def _admit(e, rid=0, plen=6, n_new=3):
    req = Request(rid=rid, prompt=np.zeros((1, plen), np.int32),
                  max_new_tokens=n_new)
    e.admit(req)
    return req


def test_engine_step_equals_dispatch_collect():
    """step() must be exactly dispatch()+collect(), and dispatching twice
    without collecting is a bug the engine refuses."""
    e = _engine()
    _admit(e, n_new=3)
    assert e.dispatch()
    assert e.pending_collect
    with pytest.raises(RuntimeError, match="uncollected"):
        e.dispatch()
    done = e.collect()
    assert e.pending_collect is False
    done += e.run_to_completion()
    assert len(done) == 1 and done[0].status == "ok"


def test_dispatch_returns_false_when_idle():
    e = _engine()
    assert e.dispatch() is False
    assert e.collect() == []


def test_ewma_updates_at_collect_not_dispatch():
    """Satellite: the tok/s EWMA must window dispatch-enqueue to
    collect-sync, so it only moves once the round's results landed."""
    e = _engine()
    _admit(e, n_new=4)
    assert e.dispatch()
    assert e._ewma_tok_s == 0.0      # decode round in flight, not timed yet
    e.collect()
    e.step()                         # a full decode round
    assert e._ewma_tok_s > 0.0


def test_fail_during_pending_drops_dispatched_round():
    """A crash between dispatch and collect must drop the in-flight round,
    orphan its requests, and zero the KV accounting."""
    e = _engine()
    _admit(e, n_new=4)
    assert e.dispatch()
    orphans = e.fail("injected")
    assert e._pending is None
    assert len(orphans) == 1
    assert e.kv_leak == 0
    assert e.collect() == []


# ---------------------------------------------------------------------------
# shared compiled-step cache
# ---------------------------------------------------------------------------


def test_same_config_engines_share_compiled_steps():
    compiled.clear_cache()
    a = _engine(arch="xlstm-350m", seed=0)   # dense slot pool
    b = _engine(arch="xlstm-350m", seed=1)
    assert a._prefill is b._prefill
    assert a._pool_decode is b._pool_decode
    info = compiled.cache_info()
    assert info["hits"] > 0
    p1 = _engine(arch="qwen2-1.5b", seed=0)  # paged page pool
    p2 = _engine(arch="qwen2-1.5b", seed=1)
    assert p1._paged_prefill is p2._paged_prefill
    assert p1._paged_decode is p2._paged_decode


def test_different_config_engines_do_not_share():
    compiled.clear_cache()
    a = _engine(arch="xlstm-350m", num_layers=2)
    b = _engine(arch="xlstm-350m", num_layers=3)   # different depth
    assert a._prefill is not b._prefill
    assert a._pool_decode is not b._pool_decode
    c = _engine(arch="qwen2-1.5b", kv_slots=2)
    d = _engine(arch="qwen2-1.5b", kv_slots=2, max_len=64)  # pool shape
    assert c._paged_decode is not d._paged_decode


def test_shared_steps_serve_identical_results():
    """Two engines behind ONE cached executable must still produce the
    same tokens as two independently jitted engines would: the cache may
    not entangle their states."""
    compiled.clear_cache()
    a = _engine(arch="xlstm-350m", seed=0)
    b = _engine(arch="xlstm-350m", seed=0)
    prompt = np.arange(8, dtype=np.int32)[None, :] % a.cfg.vocab_size
    ra = a.generate(prompt, 4)
    rb = b.generate(prompt, 4)
    assert np.array_equal(np.asarray(ra.tokens), np.asarray(rb.tokens))


# ---------------------------------------------------------------------------
# sharded big-model engines + mesh guard
# ---------------------------------------------------------------------------


def test_production_mesh_rejects_mismatched_device_count():
    """Satellite: asking for a 16-chip mesh on this runtime must fail
    loudly, naming the actual device count."""
    with pytest.raises(ValueError, match=str(jax.device_count())):
        make_production_mesh(shape=(4, 4), axes=("data", "model"))


def test_production_mesh_shape_axes_must_pair():
    with pytest.raises(ValueError):
        make_production_mesh(shape=(4, 4))


@pytest.mark.parametrize("arch", ["mixtral-8x22b", "dbrx-132b"])
def test_sharded_big_model_engine_serves(arch):
    """The big-model configs serve through the smoke mesh: params carry
    NamedShardings on the engine's mesh and a request completes."""
    eng = build_sharded_engine(arch, max_len=32, kv_slots=2,
                               prefill_chunk=8, seed=0)
    assert eng.mesh is not None
    shardings = {
        type(leaf.sharding).__name__
        for leaf in jax.tree_util.tree_leaves(eng.params)}
    assert shardings == {"NamedSharding"}
    meshes = {leaf.sharding.mesh
              for leaf in jax.tree_util.tree_leaves(eng.params)}
    assert meshes == {eng.mesh}
    req = Request(rid=0,
                  prompt=np.arange(6, dtype=np.int32)[None, :]
                  % eng.cfg.vocab_size,
                  max_new_tokens=3)
    eng.admit(req)
    done = eng.run_to_completion()
    assert len(done) == 1 and req.status == "ok"
    assert len(req.tokens) == 3
    assert eng.kv_leak == 0


def test_sharded_engine_matches_unsharded_tokens():
    """Smoke-mesh sharding must be semantically invisible: same config,
    same params, same prompt -> same tokens with and without the mesh."""
    cfg = dataclasses.replace(reduced(get_config("qwen2-1.5b")),
                              num_layers=2)
    params = init_params(jax.random.key(0), cfg)
    plain = ServeEngine(cfg, params, max_len=40, kv_slots=2,
                        prefill_chunk=8)
    sharded = ServeEngine(cfg, params, max_len=40, kv_slots=2,
                          prefill_chunk=8, mesh=make_smoke_mesh())
    prompt = np.arange(8, dtype=np.int32)[None, :] % cfg.vocab_size
    ra = plain.generate(prompt, 4)
    rb = sharded.generate(prompt, 4)
    assert np.array_equal(np.asarray(ra.tokens), np.asarray(rb.tokens))


def test_param_init_builds_into_shardings_equal_to_init_params():
    """Params generated directly into their smoke-mesh shardings hold
    exactly the values ``init_params`` gives for the same key.  (Both
    jitted: XLA folds the init's constant scales differently from eager
    op-by-op dispatch, a last-bit difference unrelated to placement.)"""
    cfg = dataclasses.replace(reduced(get_config("qwen2-1.5b")),
                              num_layers=2, scan_layers=True,
                              dtype="bfloat16")
    mesh = make_smoke_mesh()
    placed = compiled.param_init(cfg, mesh)(jax.random.key(7))
    want = jax.jit(init_params, static_argnums=1)(jax.random.key(7), cfg)
    flat, tree = jax.tree_util.tree_flatten(placed)
    assert tree == jax.tree_util.tree_structure(want)
    for got, ref in zip(flat, jax.tree_util.tree_leaves(want)):
        assert got.sharding.mesh == mesh
        assert got.dtype == ref.dtype
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(ref, np.float32))


_SHARDED4 = textwrap.dedent("""
    import functools, os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import dataclasses, jax, numpy as np
    from repro.configs import get_config
    from repro.kernels import ops
    from repro.kernels.ref import paged_decode_ref
    from repro.launch import sharding as shlib
    from repro.launch.mesh import make_production_mesh
    from repro.models.attention import paged_attention
    from repro.serving import compiled
    from repro.serving.engine import ServeEngine
    shape = tuple(int(n) for n in sys.argv[1].split("x"))
    cfg = dataclasses.replace(
        get_config("starcoder2-7b"), num_layers=3, d_model=256,
        num_heads=8, num_kv_heads=4, head_dim=32, d_ff=512, vocab_size=512)
    mesh = make_production_mesh(shape=shape, axes=("data", "model"))
    params = compiled.param_init(cfg, mesh)(jax.random.key(0))
    sharded = ServeEngine(cfg, params, max_len=64, kv_slots=2,
                          prefill_chunk=16, mesh=mesh)
    plain = ServeEngine(cfg, jax.device_put(params, jax.devices()[0]),
                        max_len=64, kv_slots=2, prefill_chunk=16)
    prompt = np.arange(20, dtype=np.int32)[None] % cfg.vocab_size
    a = np.asarray(sharded.generate(prompt, 8).tokens)
    b = np.asarray(plain.generate(prompt, 8).tokens)
    pools = {str(x.sharding.spec)
             for x in jax.tree_util.tree_leaves(sharded._paged_states)}
    print("POOLS", pools)
    assert pools == {"PartitionSpec(None, None, 'model')"}, pools
    assert np.array_equal(a, b), (a, b)
    assert sharded.kv_leak == 0
    # the Pallas kernel itself (interpreted) inside shard_map
    ks = jax.random.split(jax.random.key(1), 5)
    q = jax.random.normal(ks[0], (4, 8, 32))
    kp = jax.random.normal(ks[1], (9, 4, 8, 32))
    vp = jax.random.normal(ks[2], (9, 4, 8, 32))
    tables = jax.random.randint(ks[3], (4, 3), 1, 9)
    lengths = jax.random.randint(ks[4], (4,), 1, 25)
    ops.paged_flash_decode = functools.partial(ops.paged_flash_decode,
                                               interpret=True)
    with shlib.use(shlib.ShardingContext(mesh)):
        got = jax.jit(paged_attention)(q, kp, vp, tables, lengths)
    print("KERNEL", got.sharding.spec)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(paged_decode_ref(q, kp, vp, tables,
                                                     lengths)),
        atol=1e-5, rtol=1e-5)
""")


@pytest.mark.slow
@pytest.mark.parametrize("shape", ["1x4", "2x2"])
def test_sharded_paged_engine_on_four_devices_matches_unsharded(shape):
    """On a (1, 4) or (2, 2) ("data", "model") mesh of CPU devices the
    paged decode kernel call runs under shard_map — KV heads over
    'model', lanes over 'data', the pools placed to match after donated
    steps — and greedy tokens match one device; the interpreted kernel
    under the same shard_map matches paged_decode_ref."""
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    env = dict(os.environ, PYTHONPATH=src, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _SHARDED4, shape], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "POOLS" in r.stdout and "KERNEL" in r.stdout
