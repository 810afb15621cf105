"""Shared engine-cluster construction: one factory + warmup for the
launcher, the examples, and the benchmarks (so they all measure
identically configured clusters).

Fleet construction is compile-cheap: engines fetch their jitted
prefill/decode steps from the shared compiled-step cache
(``repro.serving.compiled``), so N same-config replicas cost ONE
compile, not N — ``build_engines``/``build_fleet`` at fleet scale go
from O(E) compiles to O(distinct archs x depths).

Published widths: ``build_engines(..., full_scale=True)`` and
``build_sharded_engine(..., full_scale=True)`` serve the registry config
as it stands (its own depth and widths, bf16, scan over layers); the
default is the ``reduced()`` CPU-sized variant.

Sharded big-model engines: :func:`build_sharded_engine` places a single
large config (``starcoder2-7b`` on four chips, ``mixtral-8x22b``,
``dbrx-132b``) across a mesh — params built directly into their
``launch.sharding.param_shardings`` placement, KV/recurrent state into
``state_pspecs`` — using the 1-device smoke mesh on CPU CI and
``make_production_mesh()`` on real device slices.  ``build_engines`` /
``build_fleet`` accept the same ``mesh=`` to shard every replica.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence

import jax

from repro.configs import get_config, reduced
from repro.launch.mesh import make_smoke_mesh
from repro.serving import compiled
from repro.serving.engine import ServeEngine


def default_depths(n_edge: int) -> List[int]:
    """Heterogeneous layer depths — the cluster's speed diversity."""
    return [2 + 2 * (i % 2) for i in range(n_edge)]


def _engine(cfg, seed: int, arch: str, mesh, max_len: int,
            **kw) -> ServeEngine:
    params = compiled.param_init(cfg, mesh)(jax.random.key(seed))
    return ServeEngine(cfg, params, max_len=max_len, arch_id=arch,
                       mesh=mesh, **kw)


def build_engines(arch: str, n_edge: int, max_len: int, *,
                  kv_slots: int = 4, sample: bool = False,
                  depths: Optional[Sequence[int]] = None,
                  seed0: int = 0, paged: Optional[bool] = None,
                  page_size: int = 16, num_pages: Optional[int] = None,
                  max_lanes: Optional[int] = None,
                  prefill_chunk: int = 64,
                  prefix_cache: Optional[bool] = None,
                  full_scale: bool = False,
                  mesh=None) -> List[ServeEngine]:
    """n_edge replicas of ``arch``, engine i seeded ``seed0 + i``.

    By default each replica is the reduced config at its own depth
    (``depths``, else :func:`default_depths`).  ``full_scale=True``
    serves the published config unchanged — every engine at the
    config's own depth and dtype — and then ``depths`` must be None.
    ``paged=None`` auto-selects the shared page pool on all-attention
    configs and the dense slot pool elsewhere; ``num_pages`` sizes that
    pool (default: ``kv_slots`` requests of ``max_len``), and the
    remaining paged knobs are ignored by dense engines.  Same-config
    replicas share compiled steps through the module cache."""
    if full_scale:
        if depths is not None:
            raise ValueError("full_scale serves the published depth; "
                             "depths does not apply")
        cfgs = [get_config(arch)] * n_edge
    else:
        depths = (list(depths) if depths is not None
                  else default_depths(n_edge))
        cfgs = [dataclasses.replace(reduced(get_config(arch)),
                                    num_layers=depths[i])
                for i in range(n_edge)]
    return [_engine(cfg, seed0 + i, arch, mesh, max_len, kv_slots=kv_slots,
                    sample=sample, paged=paged, page_size=page_size,
                    num_pages=num_pages, max_lanes=max_lanes,
                    prefill_chunk=prefill_chunk, prefix_cache=prefix_cache)
            for i, cfg in enumerate(cfgs)]


def build_fleet(archs: Sequence[str], max_len: int, *,
                kv_slots: int = 4, sample: bool = False,
                depths: Optional[Sequence[int]] = None,
                seed0: int = 0, paged: Optional[bool] = None,
                page_size: int = 16, max_lanes: Optional[int] = None,
                prefill_chunk: int = 64,
                prefix_cache: Optional[bool] = None,
                mesh=None) -> List[ServeEngine]:
    """Heterogeneous fleet: one engine PER ENTRY of ``archs``.

    Unlike :func:`build_engines` (n replicas of one arch), each engine
    here hosts a different reduced model-zoo config — mixed arch
    families mean mixed KV backends (paged attention pools next to
    dense xLSTM/RG slot pools) behind the same cluster interface.  The
    engine's ``arch_id`` tags it for request ``model_pref`` affinity.
    Repeated (arch, depth) entries share one compiled step via the
    module-level cache."""
    archs = list(archs)
    depths = (list(depths) if depths is not None
              else default_depths(len(archs)))
    return [_engine(dataclasses.replace(reduced(get_config(arch)),
                                        num_layers=depths[i]),
                    seed0 + i, arch, mesh, max_len, kv_slots=kv_slots,
                    sample=sample, paged=paged, page_size=page_size,
                    max_lanes=max_lanes, prefill_chunk=prefill_chunk,
                    prefix_cache=prefix_cache)
            for i, arch in enumerate(archs)]


def build_sharded_engine(arch: str, max_len: int, *, mesh=None,
                         full_scale: bool = False,
                         num_layers: Optional[int] = None,
                         kv_slots: int = 4, sample: bool = False,
                         paged: Optional[bool] = None, page_size: int = 16,
                         max_lanes: Optional[int] = None,
                         prefill_chunk: int = 64,
                         prefix_cache: Optional[bool] = None,
                         seed: int = 0) -> ServeEngine:
    """One BIG-model engine with params + KV placed across a mesh.

    This is the serving entry point for the configs a single chip cannot
    hold (``starcoder2-7b`` in bf16 with its KV pool, ``mixtral-8x22b``,
    ``dbrx-132b``): parameters shard via the path-based
    ``param_shardings`` rules (tensor-parallel 'model' + FSDP 'data') and
    are generated on the mesh, never whole on one chip; the KV page pool
    / dense slot pool via ``state_pspecs`` — divisibility-guarded, so
    indivisible dims replicate instead of erroring — and every
    prefill/decode step runs inside the mesh's ShardingContext.  The
    page pool splits its KV heads over 'model' only: block tables hold
    global page ids, so on a mesh with data > 1 every data replica
    holds the whole pool (``num_pages`` pages at KV / model heads).

    ``mesh=None`` uses the 1-device smoke mesh (CPU CI exercises the
    exact placement code paths); pass ``make_production_mesh()`` on a
    real slice.  ``full_scale=False`` serves the reduced config at
    ``num_layers`` (default 2: CI-sized); ``full_scale=True`` keeps the
    published widths and dtype, at the published depth unless
    ``num_layers`` cuts it."""
    if mesh is None:
        mesh = make_smoke_mesh()
    cfg = get_config(arch)
    if not full_scale:
        cfg = reduced(cfg, num_layers=num_layers or 2)
    elif num_layers is not None:
        cfg = dataclasses.replace(cfg, num_layers=num_layers)
    return _engine(cfg, seed, arch, mesh, max_len, kv_slots=kv_slots,
                   sample=sample, paged=paged, page_size=page_size,
                   max_lanes=max_lanes, prefill_chunk=prefill_chunk,
                   prefix_cache=prefix_cache)


def warmup(engines: Sequence[ServeEngine], prompt_len: int,
           gen_tokens: int = 2) -> None:
    """Compile prefill + pool decode before timed serving (handles the
    audio codebook and vision patch frontends).  Thanks to the shared
    compiled-step cache, warming one engine per distinct (config, mesh)
    warms its whole replica group.

    A paged engine with a prefix cache then serves a second prompt that
    leaves the first mid-page, so the copy-on-write page copy compiles
    here too and not at the first fork of a timed run."""
    for e in engines:
        cfg = e.cfg
        shape = ((1, cfg.num_codebooks, prompt_len) if cfg.num_codebooks
                 else (1, prompt_len))
        warm = jax.random.randint(jax.random.key(1), shape, 0,
                                  cfg.vocab_size)
        patches = None
        if cfg.vision_patches:
            patches = jax.random.normal(
                jax.random.key(2), (1, cfg.vision_patches, cfg.vision_dim))
        e.generate(warm, max(gen_tokens, 2), patches=patches)
        fork = e.page_size // 2 if e.paged else 0
        if e.prefix_cached_pages and 0 < fork < prompt_len - 1:
            e.generate(warm.at[..., fork:].add(1) % cfg.vocab_size,
                       max(gen_tokens, 2), patches=patches)
        e.reset()
