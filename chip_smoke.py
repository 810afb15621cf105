"""Chip smoke test: published-width models served on a TPU through the
system's normal entry points.

    python3 chip_smoke.py            # one chip
    python3 chip_smoke.py --chips 4  # four chips, the sharded path only

One chip: two qwen2-1.5b engines at the published config (28 layers,
bf16, layers under one scan, seeded random weights) are built by
``build_engines(full_scale=True)``; LAD-TS trains in the simulator for
one episode (as ``launch/serve.py --scheduler lad-ts --train-episodes 1``
does) and places 8 requests through ``EdgeCluster.run``: 128-token
prompts, 32 new tokens each, every other one opening with a shared
72-token prefix, so prefix hits and a copy-on-write fork happen (a
72-token prefix ends mid-page; a page-aligned 64 would never fork).

Four chips: starcoder2-7b at the published config, sharded over a
(1, 4) ("data", "model") mesh by ``build_sharded_engine``, answers 4
requests, and its kernel call — inside ``shard_map`` — must match
``paged_decode_ref`` at the served shapes.  Then the same config cut to
8 layers prefills the 4 prompts on the mesh and unsharded on chip 0,
and the first paged decode step's logits must agree.

The run fails, with no result line, unless JAX's first device is a TPU.
Any failed check exits 1.  Otherwise the last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Compiled programs are cached in ``$JAX_COMPILATION_CACHE_DIR`` when it
is set, else in ``.jax_cache/`` next to this file.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.cluster import EdgeCluster, Request  # noqa: E402
from repro.faults.policy import Health  # noqa: E402
from repro.kernels import ref  # noqa: E402
from repro.launch import sharding as shlib  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.serve import build_scheduler  # noqa: E402
from repro.models.attention import paged_attention  # noqa: E402
from repro.serving.builders import (build_engines,  # noqa: E402
                                    build_sharded_engine, warmup)
from repro.serving.engine import ServeEngine  # noqa: E402

PROMPT, NEW, PREFIX = 128, 32, 72
PAGES = 4096                    # 16-token pages: ~1.9 GB of qwen2-1.5b KV
BF16_ATOL = 2e-2                # kernel vs oracle, bf16 outputs of O(1)
LOGIT_RTOL = 2e-2               # sharded vs unsharded, relative L2


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class Checks:
    """Collects every failed check so one chip run reports them all."""

    def __init__(self):
        self.failed = []

    def __call__(self, ok, what: str) -> None:
        log(f"{'ok  ' if ok else 'FAIL'} {what}")
        if not ok:
            self.failed.append(what)


def _prompts(n, vocab, seed, shared_prefix=False):
    """n seeded (1, PROMPT) prompts; with ``shared_prefix`` every other
    one opens with one common PREFIX-token prefix."""
    key = jax.random.key(seed)
    shared = jax.random.randint(jax.random.fold_in(key, n), (1, PREFIX), 0,
                                vocab, jnp.int32)
    out = []
    for r in range(n):
        p = jax.random.randint(jax.random.fold_in(key, r), (1, PROMPT), 0,
                               vocab, jnp.int32)
        if shared_prefix and r % 2 == 0:
            p = jnp.concatenate([shared, p[:, PREFIX:]], axis=1)
        out.append(p)
    return out


def _param_bytes(params) -> int:
    return sum(x.nbytes for x in jax.tree_util.tree_leaves(params))


def _peak_bytes():
    return [d.memory_stats().get("peak_bytes_in_use")
            for d in jax.local_devices()]


def _decode_holds_kernel(engine: ServeEngine) -> bool:
    """Whether the engine's own compiled paged decode program contains
    the Pallas kernel (a Mosaic ``tpu_custom_call``)."""
    L, W = engine.max_lanes, engine._row_width
    batch = {"tokens": jnp.zeros((L, 1), jnp.int32),
             "block_tables": jnp.zeros((L, W), jnp.int32),
             "lengths": jnp.zeros((L,), jnp.int32)}
    engine._ensure_paged_states()
    with engine._sharded():
        text = engine._paged_decode.lower(
            engine.params, batch, engine._paged_states,
            jax.random.key(0)).compile().as_text()
    return "tpu_custom_call" in text


def _kernel_err(engine: ServeEngine, seed: int, mesh=None) -> float:
    """Max |kernel - paged_decode_ref| at the engine's served shapes (its
    lanes, block-table width and page pool).  With ``mesh`` the kernel
    runs as a sharded decode step runs it: inside ``shard_map`` under the
    mesh's ShardingContext."""
    cfg = engine.cfg
    L, W, ps = engine.max_lanes, engine._row_width, engine.page_size
    ks = jax.random.split(jax.random.key(seed), 5)
    dt = jnp.dtype(cfg.dtype)
    q = jax.random.normal(ks[0], (L, cfg.num_heads, cfg.head_dim), dt)
    pool = (engine.num_pages, cfg.num_kv_heads, ps, cfg.head_dim)
    kp = jax.random.normal(ks[1], pool, dt)
    vp = jax.random.normal(ks[2], pool, dt)
    tables = jax.random.randint(ks[3], (L, W), 1, engine.num_pages)
    lengths = jax.random.randint(ks[4], (L,), 1, W * ps + 1)
    ctx = shlib.ShardingContext(mesh) if mesh is not None else None
    with shlib.use(ctx):
        got = jax.jit(paged_attention)(q, kp, vp, tables, lengths)
    want = ref.paged_decode_ref(q, kp, vp, tables, lengths)
    return float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                 - want.astype(jnp.float32))))


def _first_decode_logits(engine: ServeEngine, prompts, seed: int):
    """Logits of the first paged decode step after every prompt is
    prefilled into pages of its own, through the engine's own compiled
    prefill-chunk and decode steps (on its mesh when it has one)."""
    n, plen = prompts.shape
    L, W, C = engine.max_lanes, engine._row_width, engine.prefill_chunk
    per = plen // engine.page_size + 1     # pages for plen + 1 tokens
    assert n <= L and 1 + n * per <= engine.num_pages and plen % C == 0
    tables = np.zeros((L, W), np.int32)
    for r in range(n):
        tables[r, :per] = 1 + r * per + np.arange(per)
    toks = np.zeros((L, 1), np.int32)
    toks[:n, 0] = np.asarray(jax.random.randint(
        jax.random.key(seed), (n,), 0, engine.cfg.vocab_size))
    lengths = np.zeros((L,), np.int32)
    lengths[:n] = plen
    engine._ensure_paged_states()
    states = engine._paged_states
    with engine._sharded():
        for r in range(n):
            for c0 in range(0, plen, C):
                _, states = engine._paged_prefill(
                    engine.params,
                    {"tokens": prompts[r:r + 1, c0:c0 + C],
                     "start": jnp.asarray(c0, jnp.int32),
                     "block_table": jnp.asarray(tables[r])}, states)
        logits, _, states = engine._paged_decode(
            engine.params,
            {"tokens": jnp.asarray(toks), "block_tables": jnp.asarray(tables),
             "lengths": jnp.asarray(lengths)}, states, jax.random.key(seed))
    engine._paged_states = states
    return np.asarray(logits, np.float32).reshape(L, -1)[:n]


def one_chip(seed: int, check: Checks) -> None:
    arch, edges, nreq = "qwen2-1.5b", 2, 8
    t0 = time.perf_counter()
    engines = build_engines(arch, edges, PROMPT + NEW, full_scale=True,
                            num_pages=PAGES, seed0=seed)
    jax.block_until_ready([e.params for e in engines])
    cfg = engines[0].cfg
    log(f"built {edges} x {cfg.name}: {cfg.num_layers} layers, "
        f"d_model {cfg.d_model}, {cfg.dtype}, scan_layers "
        f"{cfg.scan_layers}, {sum(_param_bytes(e.params) for e in engines)}"
        f" param bytes, {time.perf_counter() - t0:.2f} s")

    t0 = time.perf_counter()
    warmup(engines, PROMPT)
    log(f"compile + warmup {time.perf_counter() - t0:.2f} s")

    t0 = time.perf_counter()
    scheduler = build_scheduler("lad-ts", edges, 1, seed)
    log(f"LAD-TS in-sim training {time.perf_counter() - t0:.2f} s")

    trace = [Request(rid=r, prompt=p, max_new_tokens=NEW, arrival_s=0.25 * r,
                     origin=r % edges)
             for r, p in enumerate(_prompts(nreq, cfg.vocab_size, seed,
                                            shared_prefix=True))]
    cluster = EdgeCluster(engines, scheduler, seed=seed)
    t0 = time.perf_counter()
    done = cluster.run(trace)
    log(f"served {len(done)} requests in {time.perf_counter() - t0:.3f} s")
    for r in sorted(done, key=lambda r: r.rid):
        if r.done:
            log(f"req {r.rid}: engine={r.engine_id} "
                f"queue={r.queue_s * 1e3:.3f}ms "
                f"prefill={r.prefill_s * 1e3:.3f}ms "
                f"decode={r.decode_s * 1e3:.3f}ms "
                f"service={r.service_s * 1e3:.3f}ms "
                f"prefix_tokens={r.prefix_tokens}")
        else:
            log(f"req {r.rid}: {r.status} ({r.fail_reason})")

    check(len(done) == nreq and all(r.status == "ok" for r in done),
          f"all {nreq} requests ok")
    toks = [np.concatenate([np.ravel(t) for t in r.tokens]) for r in done]
    check(all(t.size == NEW for t in toks), f"{NEW} tokens per request")
    check(all(((t >= 0) & (t < cfg.vocab_size)).all() for t in toks),
          "tokens inside the vocabulary")
    check(cluster.fault_stats["quarantined"] == 0, "no engine quarantined")
    check(all(e.health is Health.HEALTHY for e in engines),
          "every engine HEALTHY")
    check(all(e.kv_leak == 0 for e in engines), "kv_leak == 0 everywhere")
    hits = sum(e.prefix_hits for e in engines)
    forks = sum(e.cow_forks for e in engines)
    check(hits > 0 and forks > 0,
          f"prefix cache hit ({hits}) and copy-on-write fork ({forks})")
    check(_decode_holds_kernel(engines[0]),
          "compiled paged decode holds tpu_custom_call")
    err = _kernel_err(engines[0], seed)
    check(err <= BF16_ATOL,
          f"Pallas kernel vs paged_decode_ref at served shapes: max abs "
          f"err {err} <= {BF16_ATOL}")


def four_chips(seed: int, check: Checks) -> None:
    arch, nreq = "starcoder2-7b", 4
    mesh = make_production_mesh(shape=(1, 4), axes=("data", "model"))
    t0 = time.perf_counter()
    eng = build_sharded_engine(arch, PROMPT + NEW, mesh=mesh,
                               full_scale=True, seed=seed)
    jax.block_until_ready(eng.params)
    cfg = eng.cfg
    per_chip = {}
    for x in jax.tree_util.tree_leaves(eng.params):
        for s in x.addressable_shards:
            per_chip[s.device.id] = per_chip.get(s.device.id, 0) + \
                s.data.nbytes
    log(f"built {cfg.name}: {cfg.num_layers} layers, {cfg.dtype}, "
        f"param bytes per chip {per_chip}, "
        f"{time.perf_counter() - t0:.2f} s")
    prompts = jnp.concatenate(_prompts(nreq, cfg.vocab_size, seed), axis=0)
    t0 = time.perf_counter()
    res = eng.generate(prompts, NEW)
    tok = np.stack([np.asarray(t) for t in res.tokens], axis=1)
    log(f"served {nreq} requests (compile included) in "
        f"{time.perf_counter() - t0:.3f} s")
    t0 = time.perf_counter()
    eng.generate(prompts, NEW)
    log(f"served them again in {time.perf_counter() - t0:.3f} s")
    check(tok.shape == (nreq, NEW), f"{nreq} x {NEW} tokens")
    check(((tok >= 0) & (tok < cfg.vocab_size)).all(),
          "tokens inside the vocabulary")
    check(eng.kv_leak == 0, "kv_leak == 0")
    check(_decode_holds_kernel(eng),
          "compiled sharded paged decode holds tpu_custom_call")
    err = _kernel_err(eng, seed, mesh)
    check(err <= BF16_ATOL,
          f"shard_map'd Pallas kernel vs paged_decode_ref at the served "
          f"shapes: max abs err {err} <= {BF16_ATOL}")
    del eng, res

    # the same config cut to 8 layers: on the mesh, then on chip 0 alone
    cut = build_sharded_engine(arch, PROMPT + NEW, mesh=mesh,
                               full_scale=True, num_layers=8, seed=seed)
    plain = ServeEngine(cut.cfg, jax.device_put(cut.params,
                                                jax.devices()[0]),
                        max_len=PROMPT + NEW, arch_id=arch)
    a, b = (_first_decode_logits(e, prompts, seed) for e in (cut, plain))
    rel = float(np.linalg.norm(a - b) / np.linalg.norm(b))
    top1 = float((a.argmax(-1) == b.argmax(-1)).mean())
    log(f"8-layer first decode-step logits: relative L2 {rel}, top-1 "
        f"agreement {top1}")
    check(rel <= LOGIT_RTOL,
          f"8 layers, first decode step sharded vs unsharded: logits "
          f"relative L2 {rel} <= {LOGIT_RTOL}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the starcoder2-7b sharded path")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    log(f"compile cache: {enable_compile_cache()}")
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    log(f"device {dev.device_kind} x {len(devices)}")
    check = Checks()
    t0 = time.perf_counter()
    (four_chips if args.chips == 4 else one_chip)(args.seed, check)
    log(f"phase wall time {time.perf_counter() - t0:.2f} s, peak bytes in "
        f"use per device {_peak_bytes()}")
    if check.failed:
        log(f"{len(check.failed)} check(s) failed")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
