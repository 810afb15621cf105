"""Shared compiled-step cache: one jitted executable per (config, shape).

Every ``ServeEngine`` used to wrap its own ``jax.jit(make_*_step(cfg))``,
so an E-engine fleet paid E identical compiles (and ``build_fleet`` at
scale re-jitted the same reduced config once per replica).  The cache
here is module level and keyed on the full *step identity* —

    (kind, cfg, max_len / kv_slots / num_pages+page_size, sample,
     temperature, mesh)

— so the N same-arch engines in a fleet share ONE jitted callable, and
jax's own executable cache then shares the compiled program across them:
fleet construction goes from O(E) compiles to O(distinct archs).  The
``cfg`` key is the frozen ``ModelConfig`` dataclass itself (hashable by
value), so two engines share a wrapper exactly when their configs are
equal; ``mesh`` participates because sharding constraints are baked in
at trace time.

Decode-round states are DONATED (``donate_argnums``): the per-round KV
pool / recurrent-state output reuses the input buffer in place instead
of allocating a fresh multi-MB copy every token — the engine always
rebinds its state reference to the step's output, so the invalidated
input is never read again.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.launch.sharding import param_shardings, state_shardings
from repro.models.transformer import abstract_params, init_params
from repro.train.steps import (make_decode_step, make_paged_decode_step,
                               make_paged_prefill_step, make_prefill_step)

_CACHE: Dict[Tuple, Callable] = {}
_STATS = {"hits": 0, "misses": 0}


def _get(key: Tuple, build: Callable[[], Callable]) -> Callable:
    fn = _CACHE.get(key)
    if fn is None:
        _STATS["misses"] += 1
        fn = _CACHE[key] = build()
    else:
        _STATS["hits"] += 1
    return fn


def cache_info() -> Dict[str, Any]:
    """Snapshot for tests / diagnostics: entry keys + hit counters."""
    return {"size": len(_CACHE), "keys": list(_CACHE), **_STATS}


def clear_cache() -> None:
    _CACHE.clear()
    _STATS["hits"] = _STATS["misses"] = 0


def param_init(cfg, mesh=None) -> Callable:
    """Jitted ``init_params(key, cfg)`` whose every leaf is built directly
    into its ``param_shardings`` placement on ``mesh`` (the default
    device when None): a model too big for one chip never exists whole
    on chip 0 (nor do the per-layer copies the scan stacking makes)."""
    def build():
        shardings = (param_shardings(mesh, abstract_params(cfg))
                     if mesh is not None else None)
        return jax.jit(functools.partial(init_params, cfg=cfg),
                       out_shardings=shardings)

    return _get(("param_init", cfg, mesh), build)


def state_zeros(abstract, mesh) -> Callable:
    """Jitted zero KV / recurrent state for a ShapeDtypeStruct tree, built
    directly into its ``state_shardings`` placement on ``mesh``."""
    leaves, tree = jax.tree_util.tree_flatten(abstract)
    key = tuple((a.shape, a.dtype) for a in leaves)

    def build():
        def zeros():
            return jax.tree_util.tree_unflatten(
                tree, [jnp.zeros(shape, dtype) for shape, dtype in key])

        return jax.jit(zeros, out_shardings=state_shardings(mesh, abstract))

    return _get(("state_zeros", tree, key, mesh), build)


# ---------------------------------------------------------------------------
# dense slot-pool steps
# ---------------------------------------------------------------------------


def prefill_step(cfg, max_len: int, mesh=None) -> Callable:
    """Jitted batch-1 prefill (returns logits + fresh per-request state)."""
    return _get(("prefill", cfg, max_len, mesh),
                lambda: jax.jit(make_prefill_step(cfg, max_len=max_len)))


def pool_insert() -> Callable:
    """Jitted slot insert ``pool.at[i].set(state)`` (structure-agnostic:
    jax retraces per state pytree, the wrapper is shared by everyone)."""
    return _get(("insert",), lambda: jax.jit(
        lambda pool, s, i: jax.tree_util.tree_map(
            lambda p_, s_: p_.at[i].set(s_), pool, s)))


def pool_decode_step(cfg, kv_slots: int, sample: bool, temperature: float,
                     mesh=None) -> Callable:
    """Jitted one-token decode round vmapped over the dense slot pool.

    signature: (params, toks (slots, 1, ...), pool_states, keys) ->
    (tokens, new_pool_states); ``pool_states`` is donated (the round
    rewrites the pool in place instead of copying it)."""
    def build():
        dec = make_decode_step(cfg, sample=sample, temperature=temperature)

        def pool_step(params, toks, states, keys):
            def one(p, tk, st_, k):
                if sample:
                    _, tok, ns = dec(p, {"tokens": tk}, st_, rng=k)
                else:
                    _, tok, ns = dec(p, {"tokens": tk}, st_)
                return tok, ns

            return jax.vmap(one, in_axes=(None, 0, 0, 0))(
                params, toks, states, keys)

        return jax.jit(pool_step, donate_argnums=2)

    return _get(("pool_decode", cfg, kv_slots, sample, temperature, mesh),
                build)


# ---------------------------------------------------------------------------
# paged page-pool steps
# ---------------------------------------------------------------------------


def paged_prefill_step(cfg, num_pages: int, page_size: int,
                       mesh=None) -> Callable:
    """Jitted one-chunk paged prefill; the shared page pools are donated
    (each chunk rewrites a few pages of a large pool — copying the whole
    pool per chunk would dwarf the chunk's own compute)."""
    return _get(("paged_prefill", cfg, num_pages, page_size, mesh),
                lambda: jax.jit(make_paged_prefill_step(cfg),
                                donate_argnums=2))


def paged_decode_step(cfg, num_pages: int, page_size: int, sample: bool,
                      temperature: float, mesh=None) -> Callable:
    """Jitted paged decode round (donated page pools, same rationale)."""
    return _get(
        ("paged_decode", cfg, num_pages, page_size, sample, temperature,
         mesh),
        lambda: jax.jit(make_paged_decode_step(cfg, sample=sample,
                                               temperature=temperature),
                        donate_argnums=2))


def page_copy_step(cfg, num_pages: int, page_size: int, mesh=None) -> Callable:
    """Jitted copy-on-write page fork: every per-layer K/V pool copies
    physical page ``src`` onto page ``dst`` in place (donated states, and
    src/dst are traced scalars so one compile covers every fork).  Used
    when a lane's prompt diverges MID-block from a cached prefix: the
    matched head of the cached page is duplicated so the lane can
    overwrite its private tail without corrupting the shared original."""
    def build():
        def copy(states, src, dst):
            return jax.tree_util.tree_map(
                lambda a: a.at[dst].set(a[src]), states)

        return jax.jit(copy, donate_argnums=0)

    return _get(("page_copy", cfg, num_pages, page_size, mesh), build)
