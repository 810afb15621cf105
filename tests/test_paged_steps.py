"""The compiled paged steps keep no copy of a KV pool.

The paged decode and prefill steps carry the stacked page pools through
the layer scan and write each token's rows in place, so the donated pools
are their own output and the steps' temporaries hold no pool: compiled
here at a tiny size, their temporaries stay below one layer's K pool.  A
step that scans the pools in as xs and out as ys holds a whole stacked
pool as temporaries, many times that.

The pools are made large against the weights (2,048 pages a layer) so
that a pool-sized buffer cannot hide behind the per-layer weight slices
the CPU backend keeps."""
import contextlib
import dataclasses
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config, reduced
from repro.launch import sharding as shlib
from repro.models.transformer import abstract_params, init_paged_states
from repro.serving import compiled

PAGES, PAGE, LANES, ROW, CHUNK = 2048, 16, 4, 8, 16

# (arch, layers, changes): starcoder2's dense GQA layer (8 query heads
# on 4 KV heads, which a (1, 4) mesh splits), scanned in four groups; a
# Mellum2 pattern of three windowed and one full layer, twice (no
# unscanned remainder, four stacked pools a kind)
_CONFIGS = {
    "dense": ("starcoder2-7b", 4, {"num_heads": 8, "num_kv_heads": 4,
                                   "head_dim": 32}),
    "window-full": ("mellum2-12b-a2.5b-d12", 8, {}),
}


def step_temps(case: str, mesh=None) -> dict:
    """Temporaries of the compiled paged decode and prefill steps, and
    the bytes of one layer's K pool (per device on ``mesh``)."""
    arch, layers, changes = _CONFIGS[case]
    cfg = dataclasses.replace(reduced(get_config(arch), num_layers=layers),
                              scan_layers=True, **changes)
    params = abstract_params(cfg)
    states = init_paged_states(cfg, PAGES, PAGE, make=jax.ShapeDtypeStruct)
    S, i32 = jax.ShapeDtypeStruct, jnp.int32
    decode = {"tokens": S((LANES, 1), i32),
              "block_tables": S((LANES, ROW), i32),
              "lengths": S((LANES,), i32)}
    prefill = {"tokens": S((1, CHUNK), i32), "block_table": S((ROW,), i32),
               "start": S((), i32)}
    kv_heads = cfg.num_kv_heads
    if mesh is not None:
        def put(tree, shardings):
            return jax.tree_util.tree_map(
                lambda a, s: S(a.shape, a.dtype, sharding=s), tree,
                shardings)

        params = put(params, shlib.param_shardings(mesh, params))
        states = put(states, shlib.state_shardings(mesh, states))
        kv_heads //= mesh.shape["model"]
    steps = {"decode": (compiled.paged_decode_step(
                 cfg, PAGES, PAGE, False, 1.0, mesh=mesh), decode),
             "prefill": (compiled.paged_prefill_step(
                 cfg, PAGES, PAGE, mesh=mesh), prefill)}
    out = {"layer_k_pool": (PAGES * kv_heads * PAGE * cfg.head_dim
                            * jnp.dtype(cfg.dtype).itemsize)}
    for name, (step, batch) in steps.items():
        with (shlib.use(shlib.ShardingContext(mesh)) if mesh is not None
              else contextlib.nullcontext()):
            done = step.lower(params, batch, states).compile()
        out[name] = done.memory_analysis().temp_size_in_bytes
    return out


_ON_FOUR = textwrap.dedent("""
    import json, os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path[:0] = [sys.argv[1]]
    from repro.launch.mesh import make_production_mesh
    from test_paged_steps import step_temps
    mesh = make_production_mesh(shape=(1, 4), axes=("data", "model"))
    print("TEMPS", json.dumps(step_temps(sys.argv[2], mesh)))
""")


@pytest.mark.parametrize("case", ["dense", "window-full", "dense-1x4"])
def test_paged_steps_hold_no_pool_copy(case):
    """Compiled paged decode and prefill steps: temporaries below one
    layer's K pool, on one device and per device on a (1, 4) mesh."""
    if case.endswith("-1x4"):
        here = os.path.dirname(os.path.abspath(__file__))
        src = os.path.join(here, "..", "src")
        env = dict(os.environ, PYTHONPATH=src, JAX_PLATFORMS="cpu")
        env.pop("XLA_FLAGS", None)
        r = subprocess.run([sys.executable, "-c", _ON_FOUR, here,
                            case[:-len("-1x4")]], env=env,
                           capture_output=True, text=True, timeout=600)
        assert r.returncode == 0, r.stdout + r.stderr[-4000:]
        line = [x for x in r.stdout.splitlines() if x.startswith("TEMPS ")]
        temps = json.loads(line[-1].split(" ", 1)[1])
    else:
        temps = step_temps(case)
    pool = temps["layer_k_pool"]
    assert temps["decode"] < pool, temps
    assert temps["prefill"] < pool, temps
