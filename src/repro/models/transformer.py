"""Unified decoder: one model covering all six assigned arch families.

A model is a stack of blocks described by ``cfg.layer_pattern()``.  Layers
are evaluated either
  * flat (scan_layers=False; CPU smoke tests), or
  * grouped lax.scan over repeating pattern groups (scan_layers=True) —
    keeps HLO size O(1) in depth, which is what makes 512-partition
    dry-run compiles tractable.  ``num_layers % len(pattern)`` remainder
    layers are applied unscanned after the scan.

Three entry points, matched to the assigned input-shape kinds:
  * ``forward(..., mode="train")``    -> hidden states (loss lives in
    repro.train.losses, chunked so logits are never fully materialised)
  * ``forward(..., mode="prefill")``  -> last-token logits + filled caches
  * ``forward(..., mode="decode")``   -> one-token logits + updated caches

Modality carve-outs (per assignment): the audio conv-codec and the VLM
vision tower are stubs — inputs arrive as token streams / patch embeddings;
the codebook embedding sum, per-codebook heads, and multimodal projector
are implemented for real.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.launch import sharding
from repro.models import attention, moe, rglru, xlstm
from repro.models.layers import (dense_init, init_mlp, init_rmsnorm,
                                 apply_mlp, rmsnorm, sinusoidal_positions)

# ---------------------------------------------------------------------------
# parameter init
# ---------------------------------------------------------------------------


def _init_block(key, cfg, blk, dtype) -> dict:
    ks = jax.random.split(key, 3)
    p: Dict[str, Any] = {"norm1": init_rmsnorm(cfg.d_model, dtype)}
    if blk.mixer == "attn":
        p["mixer"] = attention.init_attention(ks[0], cfg, dtype)
    elif blk.mixer == "mlstm":
        p["mixer"] = xlstm.init_mlstm(ks[0], cfg, dtype)
    elif blk.mixer == "slstm":
        p["mixer"] = xlstm.init_slstm(ks[0], cfg, dtype)
    elif blk.mixer == "rglru":
        p["mixer"] = rglru.init_rglru(ks[0], cfg, dtype)
    else:
        raise ValueError(blk.mixer)
    if blk.ffn != "none":
        p["norm2"] = init_rmsnorm(cfg.d_model, dtype)
        if blk.ffn == "dense":
            p["ffn"] = init_mlp(ks[1], cfg.d_model, cfg.d_ff, cfg.gated_ffn,
                                dtype)
        elif blk.ffn == "moe":
            p["ffn"] = moe.init_moe(ks[1], cfg, dtype)
        else:
            raise ValueError(blk.ffn)
    return p


def _layer_layout(cfg) -> Tuple[int, int]:
    """(n_groups, remainder) for grouped layer scan."""
    P = len(cfg.pattern)
    return cfg.num_layers // P, cfg.num_layers % P


def init_params(key, cfg) -> dict:
    dtype = jnp.dtype(cfg.dtype)
    pattern = cfg.layer_pattern()
    P = len(cfg.pattern)
    keys = jax.random.split(key, cfg.num_layers + 4)

    params: Dict[str, Any] = {}
    # embeddings ------------------------------------------------------------
    if cfg.num_codebooks:
        emb = jnp.stack([dense_init(k, cfg.vocab_size, cfg.d_model, dtype)
                         for k in jax.random.split(keys[-1],
                                                   cfg.num_codebooks)])
    else:
        emb = dense_init(keys[-1], cfg.vocab_size, cfg.d_model, dtype)
    params["embed"] = {"embed": emb}
    if cfg.vision_patches:
        k1, k2 = jax.random.split(keys[-2])
        params["projector"] = {
            "w_proj": dense_init(k1, cfg.vision_dim, cfg.d_model, dtype),
            "w_up": dense_init(k2, cfg.d_model, cfg.d_model, dtype),
        }
    params["final_norm"] = init_rmsnorm(cfg.d_model, dtype)
    if not cfg.tie_embeddings:
        if cfg.num_codebooks:
            head = jnp.stack([dense_init(k, cfg.d_model, cfg.vocab_size,
                                         dtype)
                              for k in jax.random.split(
                                  keys[-3], cfg.num_codebooks)])
        else:
            head = dense_init(keys[-3], cfg.d_model, cfg.vocab_size, dtype)
        params["head"] = {"head": head}

    # layers ----------------------------------------------------------------
    layer_params = [
        _init_block(keys[i], cfg, pattern[i], dtype)
        for i in range(cfg.num_layers)
    ]
    if cfg.scan_layers:
        n_groups, rem = _layer_layout(cfg)
        scan, remp = [], []
        if n_groups > 0:
            for j in range(P):
                stack = [layer_params[g * P + j] for g in range(n_groups)]
                scan.append(jax.tree_util.tree_map(
                    lambda *xs: jnp.stack(xs), *stack))
        for j in range(rem):
            remp.append(layer_params[n_groups * P + j])
        params["layers"] = {"scan": scan, "rem": remp}
    else:
        params["layers"] = {"flat": layer_params}
    return params


def abstract_params(cfg) -> dict:
    """Parameter ShapeDtypeStructs without allocating (dry-run path)."""
    return jax.eval_shape(functools.partial(init_params, cfg=cfg),
                          jax.random.key(0))


# ---------------------------------------------------------------------------
# per-layer state ("KV cache" generalised to recurrent families)
# ---------------------------------------------------------------------------


def _init_block_state(cfg, blk, batch, max_len, make):
    if blk.mixer == "attn":
        return attention.init_kv_cache(cfg, blk, batch, max_len, make=make)
    if blk.mixer == "mlstm":
        return xlstm.init_mlstm_state(cfg, batch, make=make)
    if blk.mixer == "slstm":
        return xlstm.init_slstm_state(cfg, batch, make=make)
    if blk.mixer == "rglru":
        return rglru.init_rglru_state(cfg, batch, make=make)
    raise ValueError(blk.mixer)


def init_layer_states(cfg, batch: int, max_len: int, make=jnp.zeros,
                      filled_pos: Optional[int] = None) -> dict:
    """State pytree matching the params layer layout.

    ``make(shape, dtype)`` may be jnp.zeros or jax.ShapeDtypeStruct.
    ``filled_pos`` stamps a concrete token count (decode dry-runs pretend a
    ``seq_len``-deep cache is already populated).
    """
    pattern = cfg.layer_pattern()
    P = len(cfg.pattern)

    def one(blk):
        st = _init_block_state(cfg, blk, batch, max_len, make)
        if filled_pos is not None and make is jnp.zeros:
            st["pos"] = jnp.asarray(filled_pos, jnp.int32)
        return st

    if cfg.scan_layers:
        n_groups, rem = _layer_layout(cfg)
        if n_groups == 0:
            return {"scan": [],
                    "rem": [one(pattern[j]) for j in range(rem)]}

        def stacked(blk):
            base = one(blk)
            return jax.tree_util.tree_map(
                lambda leaf: (jax.ShapeDtypeStruct((n_groups,) + leaf.shape,
                                                   leaf.dtype)
                              if isinstance(leaf, jax.ShapeDtypeStruct)
                              else jnp.broadcast_to(
                                  leaf, (n_groups,) + leaf.shape)),
                base)

        return {"scan": [stacked(pattern[j]) for j in range(P)],
                "rem": [one(pattern[n_groups * P + j]) for j in range(rem)]}
    return {"flat": [one(b) for b in pattern]}


def init_paged_states(cfg, num_pages: int, page_size: int,
                      make=jnp.zeros) -> dict:
    """Per-layer shared page pools, mirroring the params layer layout.

    One (num_pages, KV, page_size, hd) k/v pool per layer; the block table
    mapping sequences to pages lives in the serving engine (it is shared
    across layers, so it is not part of this state pytree).  Only
    all-attention stacks can be paged — recurrent mixers have no KV to
    page (the engine falls back to dense slot caches for those).
    """
    pattern = cfg.layer_pattern()
    P = len(cfg.pattern)

    def one(blk):
        if blk.mixer != "attn":
            raise ValueError(
                f"paged serving requires attention mixers, got {blk.mixer}")
        return attention.init_paged_kv_cache(cfg, blk, num_pages, page_size,
                                             make=make)

    if cfg.scan_layers:
        n_groups, rem = _layer_layout(cfg)
        if n_groups == 0:
            return {"scan": [],
                    "rem": [one(pattern[j]) for j in range(rem)]}

        def stacked(blk):
            base = one(blk)
            return jax.tree_util.tree_map(
                lambda leaf: (jax.ShapeDtypeStruct((n_groups,) + leaf.shape,
                                                   leaf.dtype)
                              if isinstance(leaf, jax.ShapeDtypeStruct)
                              else jnp.broadcast_to(
                                  leaf, (n_groups,) + leaf.shape)),
                base)

        return {"scan": [stacked(pattern[j]) for j in range(P)],
                "rem": [one(pattern[n_groups * P + j]) for j in range(rem)]}
    return {"flat": [one(b) for b in pattern]}


# ---------------------------------------------------------------------------
# block application
# ---------------------------------------------------------------------------


def _apply_block(cfg, blk, p, x, positions, state, mode, max_len=None,
                 paged=None):
    """Returns (x_out, new_state, aux, tokens per expert (E,) or None)."""
    h = rmsnorm(p["norm1"], x, cfg.norm_eps)
    new_state = state
    if mode in ("paged_prefill", "paged_decode") and blk.mixer != "attn":
        raise ValueError(
            f"paged serving requires attention mixers, got {blk.mixer}")
    if blk.mixer == "attn":
        if mode == "train":
            mix = attention.attend_train(p["mixer"], cfg, blk, h, positions)
        elif mode == "prefill":
            mix, new_state = attention.prefill(p["mixer"], cfg, blk, h,
                                               positions, max_len=max_len)
        elif mode == "paged_prefill":
            mix, new_state = attention.paged_prefill_chunk(
                p["mixer"], cfg, blk, h, state, paged["block_table"],
                paged["start"], paged["layer"])
        elif mode == "paged_decode":
            mix, new_state = attention.paged_decode(
                p["mixer"], cfg, blk, h, state, paged["block_tables"],
                paged["lengths"], paged["layer"])
        else:
            mix, new_state = attention.decode(p["mixer"], cfg, blk, h, state)
    elif blk.mixer == "mlstm":
        if mode == "decode":
            mix, new_state = xlstm.mlstm_step(p["mixer"], cfg, h, state)
        else:
            mix, new_state = xlstm.mlstm_scan(p["mixer"], cfg, h)
    elif blk.mixer == "slstm":
        if mode == "decode":
            mix, new_state = xlstm.slstm_step(p["mixer"], cfg, h, state)
        else:
            mix, new_state = xlstm.slstm_scan(p["mixer"], cfg, h)
    elif blk.mixer == "rglru":
        if mode == "decode":
            mix, new_state = rglru.rglru_step(p["mixer"], cfg, h, state)
        else:
            mix, new_state = rglru.rglru_scan(
                p["mixer"], cfg, h,
                use_assoc_scan=getattr(cfg, "use_assoc_scan", False))
    else:
        raise ValueError(blk.mixer)
    x = x + mix
    aux = jnp.zeros((), jnp.float32)
    counts = None
    if blk.ffn != "none":
        h2 = rmsnorm(p["norm2"], x, cfg.norm_eps)
        if blk.ffn == "dense":
            ctx = sharding.current()
            dp = ctx.sharding("batch", None, "ff") if ctx else None
            y = apply_mlp(p["ffn"], h2, dp_spec=dp)
        else:
            y, aux, counts = moe.apply_moe(p["ffn"], cfg, h2)
        x = x + y
    x = sharding.act(x, "batch", "seq", None)
    return x, new_state, aux, counts


# ---------------------------------------------------------------------------
# embeddings & heads
# ---------------------------------------------------------------------------


def embed_inputs(params, cfg, inputs: dict, pos_offset) -> jnp.ndarray:
    emb = params["embed"]["embed"]
    if cfg.num_codebooks:
        toks = inputs["tokens"]                 # (B, K, S)
        x = sum(emb[k][toks[:, k]] for k in range(cfg.num_codebooks))
    else:
        x = emb[inputs["tokens"]]               # (B, S, d)
    if cfg.vision_patches and "patches" in inputs:
        pr = params["projector"]
        pe = jax.nn.gelu(inputs["patches"] @ pr["w_proj"]) @ pr["w_up"]
        x = jnp.concatenate([pe.astype(x.dtype), x], axis=1)
    if not cfg.use_rope:
        S = x.shape[1]
        pos = pos_offset + jnp.arange(S)   # (S,) or (B, S) if offset (B, 1)
        pe = sinusoidal_positions(pos, cfg.d_model)
        if pe.ndim == 2:
            pe = pe[None]
        x = x + pe.astype(x.dtype)
    return sharding.act(x, "batch", "seq", None)


def apply_head(params, cfg, x) -> jnp.ndarray:
    """x (B, S, d) -> logits (B, S, V) or (B, S, K, V)."""
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = x @ params["embed"]["embed"].T
    elif cfg.num_codebooks:
        logits = jnp.einsum("bsd,kdv->bskv", x, params["head"]["head"])
    else:
        logits = x @ params["head"]["head"]
    return logits


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _stack_counts(counts):
    """Per-layer expert counts, in layer order, as one (L_moe, E) array
    (None without expert layers)."""
    return jnp.concatenate(counts) if counts else None


def _apply_unscanned(cfg, blk, p, x, positions, state, mode, max_len=None,
                     paged=None):
    """``_apply_block`` on a layer outside the scan.  In the paged modes its
    pools go in as a stack of one layer, at index 0."""
    if paged is None:
        return _apply_block(cfg, blk, p, x, positions, state, mode,
                            max_len=max_len)
    stack = jax.tree_util.tree_map(lambda a: a[None], state)
    x, ns, a, c = _apply_block(cfg, blk, p, x, positions, stack, mode,
                               paged=dict(paged, layer=0))
    return x, jax.tree_util.tree_map(lambda a: a[0], ns), a, c


def _scan_layers(cfg, layers, x, positions, states, mode, max_len=None,
                 paged=None):
    """Grouped scan over layers.  Returns (x, new_states, aux_sum,
    expert counts (L_moe, E) or None).

    The paged modes carry the stacked page pools through the scan, and
    group g's layers write and read layer g of them in place: the pools
    are never sliced per layer nor rebuilt as the scan's output, so the
    donated input is the output.  The other modes scan per-group states
    in and new ones out."""
    pattern = cfg.layer_pattern()
    P = len(cfg.pattern)
    n_groups, rem = _layer_layout(cfg)
    aux_total = jnp.zeros((), jnp.float32)
    counts = []

    def group(xc, aux, gp, gs, at):
        """Positions 0..P-1 of one group; gs per position (or None)."""
        new_gs, cs = [], []
        for j in range(P):
            xc, ns, a, c = _apply_block(
                cfg, pattern[j], gp[j], xc, positions,
                gs[j] if gs is not None else None, mode,
                max_len=max_len, paged=at)
            new_gs.append(ns)
            aux = aux + a
            if c is not None:
                cs.append(c)
        return xc, aux, new_gs, cs

    new_scan_states = states["scan"] if states is not None else []
    cs = []
    if n_groups > 0 and paged is not None:
        def pools_body(carry, xs):
            xc, aux, pools = carry
            gp, g = xs
            xc, aux, pools, cs = group(xc, aux, gp, pools,
                                       dict(paged, layer=g))
            return (xc, aux, pools), cs

        (x, aux_total, new_scan_states), cs = jax.lax.scan(
            pools_body, (x, aux_total, states["scan"]),
            (layers["scan"], jnp.arange(n_groups)))
    elif n_groups > 0:
        def group_body(carry, xs):
            xc, aux = carry
            gp, gs = xs            # per-position lists stacked over groups
            xc, aux, new_gs, cs = group(xc, aux, gp, gs, None)
            return (xc, aux), (new_gs, cs)

        body = group_body
        if cfg.remat and mode == "train":
            body = jax.checkpoint(group_body,
                                  policy=jax.checkpoint_policies.nothing_saveable)
        scan_states = states["scan"] if states is not None else None
        (x, aux_total), (new_scan_states, cs) = jax.lax.scan(
            body, (x, aux_total), (layers["scan"], scan_states))
    if cs:                     # (groups, positions, E): layer g * P + j
        counts.append(jnp.stack(cs, axis=1).reshape(-1, cs[0].shape[-1]))

    new_rem = []
    for j in range(rem):
        blk = pattern[n_groups * P + j]
        st = states["rem"][j] if states is not None else None
        def blk_fn(p_, x_, st_, blk=blk):
            return _apply_unscanned(cfg, blk, p_, x_, positions, st_, mode,
                                    max_len=max_len, paged=paged)
        if cfg.remat and mode == "train":
            blk_fn = jax.checkpoint(
                blk_fn, policy=jax.checkpoint_policies.nothing_saveable)
        x, ns, a, c = blk_fn(layers["rem"][j], x, st)
        new_rem.append(ns)
        aux_total = aux_total + a
        if c is not None:
            counts.append(c[None])
    new_states = None
    if mode != "train":
        new_states = {"scan": new_scan_states, "rem": new_rem}
    return x, new_states, aux_total, _stack_counts(counts)


def _flat_layers(cfg, layers, x, positions, states, mode, max_len=None,
                 paged=None):
    pattern = cfg.layer_pattern()
    aux_total = jnp.zeros((), jnp.float32)
    new_states, counts = [], []
    for i, blk in enumerate(pattern):
        st = states["flat"][i] if states is not None else None
        x, ns, a, c = _apply_unscanned(cfg, blk, layers["flat"][i], x,
                                       positions, st, mode, max_len=max_len,
                                       paged=paged)
        new_states.append(ns)
        aux_total = aux_total + a
        if c is not None:
            counts.append(c[None])
    return (x, ({"flat": new_states} if mode != "train" else None),
            aux_total, _stack_counts(counts))


def forward(params, cfg, inputs: dict, mode: str = "train",
            states: Optional[dict] = None,
            max_len: Optional[int] = None) -> dict:
    """Run the model.

    train         : inputs {tokens[, patches]}   -> {hidden, aux}
    prefill       : inputs {tokens[, patches]}   -> {last_logits, states, aux}
    decode        : inputs {tokens} + states     -> {logits, states}
    paged_prefill : inputs {tokens (1,C), start (), block_table (W,)}
                    + paged states               -> {chunk_logits, states}
    paged_decode  : inputs {tokens (B,1), block_tables (B,W), lengths (B,)}
                    + paged states               -> {logits, states}

    With expert layers every mode also returns ``expert_tokens``, the
    tokens routed to each expert of each such layer, (L_moe, E) int32.
    """
    paged = None
    if mode == "decode":
        # positions come from the per-layer state's pos counter
        pos0 = _first_pos(states)
        x = embed_inputs(params, cfg, inputs, pos0)
    elif mode == "paged_prefill":
        paged = {"block_table": inputs["block_table"],
                 "start": inputs["start"]}
        x = embed_inputs(params, cfg, inputs, inputs["start"])
        pos0 = None
    elif mode == "paged_decode":
        paged = {"block_tables": inputs["block_tables"],
                 "lengths": inputs["lengths"]}
        x = embed_inputs(params, cfg, inputs, inputs["lengths"][:, None])
        pos0 = None
    else:
        x = embed_inputs(params, cfg, inputs, 0)
        pos0 = None
    S = x.shape[1]
    if mode == "decode":
        positions = pos0 + jnp.arange(1)
    else:
        # paged modes compute absolute positions inside the attention layer
        # (from start / lengths); this drives nothing there.
        positions = jnp.arange(S)

    run = _scan_layers if cfg.scan_layers else _flat_layers
    x, new_states, aux, counts = run(cfg, params["layers"], x, positions,
                                     states, mode, max_len=max_len,
                                     paged=paged)

    out: Dict[str, Any] = {"aux": aux}
    if counts is not None:
        out["expert_tokens"] = counts
    if mode == "train":
        out["hidden"] = x
    elif mode == "prefill":
        out["last_logits"] = apply_head(params, cfg, x[:, -1:])[:, 0]
        out["states"] = new_states
    elif mode == "paged_prefill":
        out["chunk_logits"] = apply_head(params, cfg, x)   # (1, C, V...)
        out["states"] = new_states
    else:
        out["logits"] = apply_head(params, cfg, x)[:, 0]
        out["states"] = new_states
    return out


def _first_pos(states):
    leaves = jax.tree_util.tree_leaves(
        jax.tree_util.tree_map(lambda s: s["pos"], states,
                               is_leaf=lambda s: isinstance(s, dict)
                               and "pos" in s))
    p = leaves[0]
    return p[0] if p.ndim == 1 else p  # scanned states carry a group axis


def config_for_shape(cfg, shape):
    """Long-context decode on full-attention archs switches to the
    beyond-paper sliding-window variant (weights are unchanged)."""
    if (shape.kind == "decode" and shape.seq_len > 65536
            and not cfg.is_subquadratic()):
        raise ValueError(
            f"{cfg.name} cannot serve {shape.name}: full attention and no "
            "long_context_window configured (see DESIGN.md skips)")
    if (shape.kind == "decode" and shape.seq_len > 65536
            and cfg.long_context_window is not None
            and cfg.sliding_window is None):
        return dataclasses.replace(cfg,
                                   sliding_window=cfg.long_context_window)
    return cfg
