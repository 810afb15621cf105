"""Mixture-of-Experts FFN.

Three execution paths share the same weights:

* ``_apply_moe_local`` — the single-device path (the serving engine's):
  the exact top-k softmax mixture, renormalised, with no token dropped.
  Every expert runs on every token of the call and a one-hot combine
  keeps each token's top k, so each expert's weights are read once per
  call.  At Mellum2's shapes on a v5e this beat the grouped alternatives
  that read only the chosen experts (``jax.lax.ragged_dot`` and the
  megablox ``gmm`` kernel), at 16 decode rows and at a 256-row chunk
  alike (PERF.md, findings on Mellum2).

* ``_apply_moe_sharded`` — the multi-chip adaptation, capacity-bounded
  (see DESIGN.md).  Key
  observation: with activations sharded over the data axis and *replicated*
  over the model axis, expert parallelism needs NO all-to-all: every model
  shard already holds the tokens, so shard j simply computes its owned
  expert slice(s) on its local tokens and one reduce(-scatter)/psum over
  'model' combines the top-k expert outputs.  Capacity is enforced per
  token-shard (C_loc = cf * T_loc * K / E), the standard local-capacity
  approximation.  When E < model-axis size (Mixtral 8e on 16-way TP) the
  model axis factors into (expert_parallel=gcd(E, M), ffn_parallel=M/gcd):
  each expert's FFN is column-split over ffn_parallel shards and the same
  psum accumulates the partial products.

This replaces a GSPMD scatter-based dispatch that replicated the
(T*K, d) dispatch tensors on every device (measured 15 x 12.9 GB/device
on dbrx-132b train_4k — see EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.launch import sharding
from repro.models.layers import dense_init


def init_moe(key, cfg, dtype) -> dict:
    m = cfg.moe
    d, f, E = cfg.d_model, cfg.d_expert, m.num_experts
    ks = jax.random.split(key, 4)

    def experts(k, fan_in, fan_out):      # one key per expert
        return jax.vmap(lambda ke: dense_init(ke, fan_in, fan_out, dtype))(
            jax.random.split(k, E))

    p = {
        "router": dense_init(ks[0], d, E, jnp.float32),
        "we_up": experts(ks[1], d, f),
        "we_down": experts(ks[2], f, d),
    }
    if cfg.gated_ffn:
        p["we_gate"] = experts(ks[3], d, f)
    return p


# ---------------------------------------------------------------------------
# routing pieces shared by both paths
# ---------------------------------------------------------------------------


def _route(router_w, cfg, xf):
    """xf (T, d) -> (gate_dense (T, E) f32, aux scalar, tokens routed to
    each expert (E,) int32)."""
    m = cfg.moe
    E, K = m.num_experts, m.top_k
    logits = xf.astype(jnp.float32) @ router_w
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, K)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    # dense (T, E) combine weights: w[t, e] = gate_k if idx_k == e else 0
    onehot = jax.nn.one_hot(idx, E, dtype=probs.dtype)       # (T, K, E)
    w_dense = jnp.einsum("tk,tke->te", gate, onehot)
    # Switch-style load-balance aux loss
    density = jnp.mean(onehot[:, 0], axis=0)
    density_proxy = jnp.mean(probs, axis=0)
    aux = jnp.sum(density * density_proxy) * E * m.router_aux_weight
    return w_dense, aux, expert_counts(idx, E)


def expert_counts(idx, num_experts: int) -> jnp.ndarray:
    """Tokens routed to each expert, (E,) int32, from ids (T, K)."""
    return jnp.bincount(idx.reshape(-1), length=num_experts
                        ).astype(jnp.int32)


def _expert_ffn(xb, wu, wd, wg):
    up = xb @ wu
    h = jax.nn.silu(xb @ wg) * up if wg is not None else jax.nn.gelu(up)
    return h @ wd


def _capacity(cfg, T_loc: int) -> int:
    m = cfg.moe
    c = int(math.ceil(m.capacity_factor * T_loc * m.top_k / m.num_experts))
    return max(min(c, T_loc), 1)


def _one_expert(xf, w_col, wu, wd, wg, C: int):
    """Capacity-bounded FIFO compute of one expert on local tokens.

    xf (T, d); w_col (T,) combine weights; returns (T, d) contribution.
    """
    T = xf.shape[0]
    assigned = w_col > 0
    # FIFO priority: earlier tokens win capacity slots
    priority = jnp.where(assigned, T - jnp.arange(T), 0)
    _, tok_idx = jax.lax.top_k(priority, C)
    valid = assigned[tok_idx]
    xb = xf[tok_idx] * valid[:, None].astype(xf.dtype)
    yb = _expert_ffn(xb, wu, wd, wg)
    yb = yb * (w_col[tok_idx] * valid).astype(xf.dtype)[:, None]
    out = jnp.zeros_like(xf)
    return out.at[tok_idx].add(yb)


# ---------------------------------------------------------------------------
# single-device path: exact, dropless
# ---------------------------------------------------------------------------


def _apply_moe_local(p, cfg, xf):
    """Returns (out (T, d), aux, tokens per expert (E,))."""
    w, aux, counts = _route(p["router"], cfg, xf)
    up = jnp.einsum("td,edf->etf", xf, p["we_up"])
    if "we_gate" in p:
        h = jax.nn.silu(jnp.einsum("td,edf->etf", xf, p["we_gate"])) * up
    else:
        h = jax.nn.gelu(up)
    y = jnp.einsum("etf,efd->etd", h, p["we_down"])
    out = jnp.einsum("te,etd->td", w, y.astype(jnp.float32))
    return out.astype(xf.dtype), aux, counts


# ---------------------------------------------------------------------------
# sharded path (expert parallel over the 'model' axis, no all-to-all)
# ---------------------------------------------------------------------------


def _layout_dims(cfg, M: int):
    E = cfg.moe.num_experts
    e_par = math.gcd(E, M)
    f_par = M // e_par
    r = E // e_par                      # experts per expert-parallel shard
    f_lp = cfg.d_expert // f_par
    return e_par, f_par, r, f_lp


def layout_cols(w, cfg, M):
    """(..., E, d, f) -> (..., M, r, d, f_lp)."""
    e_par, f_par, r, f_lp = _layout_dims(cfg, M)
    lead = w.shape[:-3]
    d = w.shape[-2]
    w = w.reshape(*lead, e_par, r, d, f_par, f_lp)
    perm = tuple(range(len(lead))) + tuple(
        len(lead) + i for i in (0, 3, 1, 2, 4))
    return w.transpose(perm).reshape(*lead, M, r, d, f_lp)


def layout_rows(w, cfg, M):
    """(..., E, f, d) -> (..., M, r, f_lp, d)."""
    e_par, f_par, r, f_lp = _layout_dims(cfg, M)
    lead = w.shape[:-3]
    d = w.shape[-1]
    w = w.reshape(*lead, e_par, r, f_par, f_lp, d)
    perm = tuple(range(len(lead))) + tuple(
        len(lead) + i for i in (0, 2, 1, 3, 4))
    return w.transpose(perm).reshape(*lead, M, r, f_lp, d)


def layout_cols_inv(w, cfg, M):
    """Inverse of layout_cols (for accumulated gradients)."""
    e_par, f_par, r, f_lp = _layout_dims(cfg, M)
    lead = w.shape[:-4]
    d = w.shape[-2]
    w = w.reshape(*lead, e_par, f_par, r, d, f_lp)
    perm = tuple(range(len(lead))) + tuple(
        len(lead) + i for i in (0, 2, 3, 1, 4))
    return w.transpose(perm).reshape(*lead, cfg.moe.num_experts, d,
                                     cfg.d_expert)


def layout_rows_inv(w, cfg, M):
    e_par, f_par, r, f_lp = _layout_dims(cfg, M)
    lead = w.shape[:-4]
    d = w.shape[-1]
    w = w.reshape(*lead, e_par, f_par, r, f_lp, d)
    perm = tuple(range(len(lead))) + tuple(
        len(lead) + i for i in (0, 2, 1, 3, 4))
    return w.transpose(perm).reshape(*lead, cfg.moe.num_experts,
                                     cfg.d_expert, d)


def prepare_tree(params, cfg, M: int):
    """Hoisted layout: transform every MoE weight in the params tree once
    (outside the layer x microbatch loops).  Detected downstream by the
    extra leading M dim."""
    def walk(node):
        if isinstance(node, dict):
            if "we_up" in node:
                out = dict(node)
                out["we_up"] = layout_cols(node["we_up"], cfg, M)
                if "we_gate" in node:
                    out["we_gate"] = layout_cols(node["we_gate"], cfg, M)
                out["we_down"] = layout_rows(node["we_down"], cfg, M)
                return out
            return {k: walk(v) for k, v in node.items()}
        if isinstance(node, list):
            return [walk(v) for v in node]
        return node
    return walk(params)


def unprepare_grads(grads, cfg, M: int):
    """Inverse transform for gradients accumulated in hoisted layout."""
    def walk(node):
        if isinstance(node, dict):
            if "we_up" in node:
                out = dict(node)
                out["we_up"] = layout_cols_inv(node["we_up"], cfg, M)
                if "we_gate" in node:
                    out["we_gate"] = layout_cols_inv(node["we_gate"], cfg,
                                                     M)
                out["we_down"] = layout_rows_inv(node["we_down"], cfg, M)
                return out
            return {k: walk(v) for k, v in node.items()}
        if isinstance(node, list):
            return [walk(v) for v in node]
        return node
    return walk(grads)


def _apply_moe_sharded(p, cfg, xf, ctx) -> Tuple[jnp.ndarray, jnp.ndarray,
                                                 jnp.ndarray]:
    mesh = ctx.mesh
    M = mesh.shape["model"]
    e_par, f_par, r, f_lp = _layout_dims(cfg, M)

    if p["we_up"].ndim == 4:            # hoisted layout (M, r, d, f_lp)
        wu = p["we_up"]
        wg = p.get("we_gate")
        wd = p["we_down"]
    else:
        wu = layout_cols(p["we_up"], cfg, M)
        wg = layout_cols(p["we_gate"], cfg, M) if "we_gate" in p else None
        wd = layout_rows(p["we_down"], cfg, M)

    dp = ctx.rules.get("batch")
    dp_axes = dp if isinstance(dp, tuple) else ((dp,) if dp else ())
    tok_spec = P(dp, None)
    gated = wg is not None

    def body(x_loc, router_w, wu_l, wd_l, wg_l):
        # x_loc (T_loc, d); w*_l (1, r, ...) local expert slices
        w_dense, aux, counts = _route(router_w, cfg, x_loc)
        C = _capacity(cfg, x_loc.shape[0])
        j = jax.lax.axis_index("model")
        my_e_par = j // f_par
        out = jnp.zeros_like(x_loc)
        for q in range(r):
            # weight layout from cols()/rows(): shard s owns experts
            # [s*r, s*r + r)  (C-order reshape over (e_par, r, ...))
            e = my_e_par * r + q
            w_col = jnp.take(w_dense, e, axis=1)
            out = out + _one_expert(
                x_loc, w_col, wu_l[0, q], wd_l[0, q],
                wg_l[0, q] if gated else None, C)
        out = jax.lax.psum(out, "model")
        # aux varies across token shards: globally mean it so the returned
        # scalar is replicated (out_specs P()).
        aux = jax.lax.pmean(aux, tuple(mesh.axis_names))
        if dp_axes:                     # this shard's tokens -> all tokens
            counts = jax.lax.psum(counts, dp_axes)
        return out, aux, counts

    in_specs = (tok_spec, P(None, None), P("model"), P("model"))
    args = [xf, p["router"], wu, wd]
    if gated:
        in_specs = in_specs + (P("model"),)
        args.append(wg)
    else:
        in_specs = in_specs + (P(None),)
        args.append(jnp.zeros((M, r, 1, 1), xf.dtype))  # unused placeholder

    fn = jax.shard_map(
        body if gated else (lambda x, rw, a, b, c: body(x, rw, a, b, None)),
        mesh=mesh, in_specs=in_specs,
        out_specs=(tok_spec, P(), P()), check_vma=False)
    out, aux, counts = fn(*args)
    return out, aux[()] if aux.ndim else aux, counts


def _apply_moe_stationary(p, cfg, xf, ctx) -> Tuple[jnp.ndarray,
                                                    jnp.ndarray,
                                                    jnp.ndarray]:
    """Weights-stationary serving path (decode-sized token counts).

    Expert weights stay fully sharded — expert-major on 'model', the d
    contraction dim on 'data' — and are NEVER gathered.  Instead the tiny
    token batch is all-gathered across the data axis (T x d bytes), every
    chip computes its (expert, d-slice) partial products, partial
    pre-activations psum over 'data', and outputs psum over both axes
    (disjoint d-slices + disjoint experts).  Per layer this replaces
    O(weights) collectives with O(tokens) ones — for dbrx decode_32k that
    is ~GB -> ~MB per step (EXPERIMENTS.md §Perf).
    """
    mesh = ctx.mesh
    M = mesh.shape["model"]
    D = mesh.shape["data"]
    e_par, f_par, r, f_lp = _layout_dims(cfg, M)
    d_model = xf.shape[-1]
    assert d_model % D == 0
    d_lp = d_model // D

    wu = p["we_up"] if p["we_up"].ndim == 4 else layout_cols(
        p["we_up"], cfg, M)
    wg = None
    if "we_gate" in p:
        wg = p["we_gate"] if p["we_gate"].ndim == 4 else layout_cols(
            p["we_gate"], cfg, M)
    wd = p["we_down"] if p["we_down"].ndim == 4 else layout_rows(
        p["we_down"], cfg, M)
    # split the d contraction dim across 'data': (M, r, d, f_lp) ->
    # (M, D, r, d_lp, f_lp); dim order puts both sharded dims in front.
    wu = wu.reshape(M, r, D, d_lp, f_lp).transpose(0, 2, 1, 3, 4)
    if wg is not None:
        wg = wg.reshape(M, r, D, d_lp, f_lp).transpose(0, 2, 1, 3, 4)
    wd = wd.reshape(M, r, f_lp, D, d_lp).transpose(0, 3, 1, 2, 4)

    dp = ctx.rules.get("batch")
    dp_axes = dp if isinstance(dp, tuple) else ((dp,) if dp else ())
    tok_spec = P(dp, None)
    gated = wg is not None

    def body(x_loc, router_w, wu_l, wd_l, wg_l):
        # x_loc (T_loc, d) -> gather the full token set (tiny).  Gathering
        # minor-axis-first makes the final row index
        # (pod*D + data) * T_loc + t, matching the slice-back below.
        x_all = x_loc
        for ax in reversed(dp_axes):
            x_all = jax.lax.all_gather(x_all, ax, axis=0, tiled=True)
        T = x_all.shape[0]
        w_dense, aux, counts = _route(router_w, cfg, x_all)
        C = _capacity(cfg, T)
        j = jax.lax.axis_index("model")
        i = jax.lax.axis_index("data")
        my_e_par = j // f_par
        di = i * d_lp
        x_slice = jax.lax.dynamic_slice_in_dim(x_all, di, d_lp, axis=1)

        out_full = jnp.zeros((T, d_model), jnp.float32)
        for q in range(r):
            e = my_e_par * r + q
            w_col = jnp.take(w_dense, e, axis=1)
            assigned = w_col > 0
            priority = jnp.where(assigned, T - jnp.arange(T), 0)
            _, tok_idx = jax.lax.top_k(priority, C)
            valid = assigned[tok_idx]
            xb = x_slice[tok_idx] * valid[:, None].astype(x_slice.dtype)
            # partial pre-activations over the local d-slice, then psum
            up = jax.lax.psum(xb @ wu_l[0, 0, q], "data")
            if gated:
                g = jax.lax.psum(xb @ wg_l[0, 0, q], "data")
                h = jax.nn.silu(g) * up
            else:
                h = jax.nn.gelu(up)
            yb = h @ wd_l[0, 0, q]                       # (C, d_lp)
            yb = yb * (w_col[tok_idx] * valid).astype(yb.dtype)[:, None]
            contrib = jnp.zeros((T, d_lp), jnp.float32)
            contrib = contrib.at[tok_idx].add(yb.astype(jnp.float32))
            out_full = jax.lax.dynamic_update_slice_in_dim(
                out_full,
                jax.lax.dynamic_slice_in_dim(out_full, di, d_lp, axis=1)
                + contrib, di, axis=1)
        # disjoint d-slices sum over 'data'; disjoint experts over 'model'
        out_full = jax.lax.psum(out_full, ("data", "model"))
        # slice back this shard's tokens
        T_loc = x_loc.shape[0]
        row = i
        if "pod" in mesh.axis_names and "pod" in dp_axes:
            row = jax.lax.axis_index("pod") * D + i
        start = row * T_loc if dp_axes else 0
        out_loc = jax.lax.dynamic_slice_in_dim(out_full, start, T_loc,
                                               axis=0)
        aux = jax.lax.pmean(aux, tuple(mesh.axis_names))
        return out_loc.astype(x_loc.dtype), aux, counts

    in_specs = (tok_spec, P(None, None), P("model", "data"),
                P("model", "data"),
                P("model", "data") if gated else P(None))
    args = [xf, p["router"], wu, wd,
            wg if gated else jnp.zeros((M, D, 1, 1, 1), xf.dtype)]
    fn = jax.shard_map(
        body if gated else (lambda x, rw, a, b, c: body(x, rw, a, b, None)),
        mesh=mesh, in_specs=in_specs, out_specs=(tok_spec, P(), P()),
        check_vma=False)
    out, aux, counts = fn(*args)
    return out, aux[()] if aux.ndim else aux, counts


def apply_moe(p: dict, cfg, x: jnp.ndarray
              ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """x (B, S, d) -> (out (B, S, d), aux_loss scalar, tokens routed to
    each expert (E,) int32)."""
    B, S, d = x.shape
    xf = x.reshape(B * S, d)
    ctx = sharding.current()
    if ctx is None or "model" not in ctx.mesh.axis_names:
        apply = _apply_moe_local
    elif (cfg.moe_stationary_serve and "data" in ctx.mesh.axis_names
            and B * S <= cfg.moe_stationary_max_tokens):
        apply = partial(_apply_moe_stationary, ctx=ctx)
    else:
        apply = partial(_apply_moe_sharded, ctx=ctx)
    out, aux, counts = apply(p, cfg, xf)
    return out.reshape(B, S, d), aux, counts
