"""End-to-end behaviour tests for the whole system.

1. A reduced model trains on the synthetic pipeline and the loss falls.
2. The scheduler routes a burst across heterogeneous ESs sensibly (the
   DEdgeAI story at smoke scale).
3. The launcher step functions lower + compile on a (1,1) mesh with the
   production sharding rules (miniature of the dry-run contract).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_shape, reduced
from repro.core import env as envlib
from repro.data.pipeline import DataConfig, synth_batch
from repro.launch import sharding as shlib
from repro.launch.mesh import make_smoke_mesh
from repro.models import init_params
from repro.train import optimizer as opt_lib
from repro.train.steps import make_eval_step, make_train_step


def test_training_reduces_loss():
    cfg = reduced(get_config("qwen2-1.5b"))
    dc = DataConfig(batch=4, seq_len=64)
    params = init_params(jax.random.key(0), cfg)
    opt_state = opt_lib.init(params)
    opt_cfg = opt_lib.AdamWConfig(learning_rate=3e-3, warmup_steps=2,
                                  total_steps=40, weight_decay=0.0)
    step = jax.jit(make_train_step(cfg, opt_cfg))
    first = None
    for s in range(40):
        params, opt_state, m = step(params, opt_state,
                                    synth_batch(cfg, dc, s))
        if first is None:
            first = float(m["loss"])
    eval_step = jax.jit(make_eval_step(cfg))
    final = float(eval_step(params, synth_batch(cfg, dc, 999)))
    assert final < first - 0.2, (first, final)


def test_scheduler_over_heterogeneous_capacity():
    """Opt-TS on a cluster with one fast ES routes most work there."""
    p = envlib.EnvParams(num_bs=3, num_slots=4, max_tasks=6,
                         f_range=(10.0, 10.0))
    ep = envlib.sample_episode(jax.random.key(0), p)
    f = np.asarray(ep.f).copy()
    f[:] = [50.0, 1.0, 1.0]
    ep = ep._replace(f=jnp.asarray(f))
    qs = envlib.init_queues(p)
    from repro.core.trainer import heuristic_actions
    counts = np.zeros(3)
    for n in range(p.max_tasks):
        a = heuristic_actions("opt-ts", p, ep, qs, 0, n, jax.random.key(n))
        qs = envlib.apply_actions(p, ep, qs, 0, n, a)
        counts += np.bincount(np.asarray(a), minlength=3)
    assert counts[0] > counts[1] + counts[2]


def test_step_functions_lower_on_mini_mesh():
    """The exact dry-run code path on a 1x1 mesh (single CPU device)."""
    from repro.launch.specs import input_specs, output_shardings
    mesh = make_smoke_mesh()
    for arch in ("qwen2-1.5b", "xlstm-350m"):
        cfg = dataclasses.replace(reduced(get_config(arch)),
                                  scan_layers=True)
        shape = dataclasses.replace(get_shape("train_4k"), seq_len=32,
                                    global_batch=2)
        ctx = shlib.ShardingContext(mesh)
        args, kwargs = input_specs(cfg, shape, mesh)
        step = make_train_step(cfg)
        with mesh:
            with shlib.use(ctx):
                out_shapes = jax.eval_shape(step, *args, **kwargs)
                outs = output_shardings(cfg, shape, mesh, out_shapes)
                compiled = jax.jit(step, out_shardings=outs).lower(
                    *args, **kwargs).compile()
        assert compiled.cost_analysis() is not None
