"""The four-chip cell's path at a tiny size on four CPU devices: a sound
run of a (1, 4)-sharded engine is correct, and the same run with the
exchange between chips left out is not."""
import json
import os
import pathlib
import subprocess
import sys
import textwrap

REPO = pathlib.Path(__file__).resolve().parents[2]

_SCRIPT = textwrap.dedent("""
    import json, os, pathlib, sys, time
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    repo, tmp = pathlib.Path(sys.argv[1]), pathlib.Path(sys.argv[2])
    sys.path[:0] = [str(repo / "src"), str(repo)]
    import jax, jax.numpy as jnp, pytest
    from jax.sharding import PartitionSpec as P
    from bench import harness
    from bench.tests import tiny
    from repro.kernels import ops
    from repro.launch import sharding
    from repro.models import attention
    from repro.serving import compiled

    mp = pytest.MonkeyPatch()
    tiny.patch_program(mp, tiny.TINY_TP4)
    root = tiny.make_root(tmp, tiny.TINY_TP4, tiny.TRAFFIC_CLOSED,
                          "tiny.closed")

    def run(tag):
        r = harness.run(root, "tiny.closed", 3, 2.0, False,
                        time.perf_counter())
        print(tag, json.dumps({"correct": r["correct"],
                               "chips": r["device"]["count"],
                               "checks": r["checks"]}), flush=True)

    run("SOUND")

    def no_exchange(q, k_pages, v_pages, block_tables, lengths):
        # every chip keeps its own heads' output and takes the other
        # chips' as zeros: the layout says replicated, nothing is sent
        ctx = sharding.current()
        pool, heads, rows, lens = sharding.paged_decode_pspecs(
            ctx, q.shape[0], k_pages.shape)
        n = ctx.mesh.shape["model"]

        def local(*a):
            out = ops.paged_flash_decode(*a)
            return jnp.concatenate([out] + [jnp.zeros_like(out)] * (n - 1),
                                   axis=1)
        return jax.shard_map(
            local, mesh=ctx.mesh, in_specs=(heads, pool, pool, rows, lens),
            out_specs=P(heads[0], None, None), check_vma=False)(
                q, k_pages, v_pages, block_tables, lengths)

    mp.setattr(attention, "paged_attention", no_exchange)
    compiled.clear_cache()
    run("NO_EXCHANGE")
""")


def test_exchange_between_chips_left_out_is_caught(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", _SCRIPT, str(REPO),
                        str(tmp_path)], capture_output=True, text=True,
                       env=env, cwd=REPO, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    got = {line.split(" ", 1)[0]: json.loads(line.split(" ", 1)[1])
           for line in p.stdout.splitlines()
           if line.startswith(("SOUND ", "NO_EXCHANGE "))}
    limit = 0.025
    sound, broken = got["SOUND"], got["NO_EXCHANGE"]
    assert sound["chips"] == broken["chips"] == 4
    assert sound["correct"], sound["checks"]
    assert not broken["correct"]
    assert broken["checks"]["max_logit_gap"]["value"] > limit
