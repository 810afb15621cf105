"""Step builders: the jit-able train / prefill / decode functions.

These are the exact functions the launcher jits on the production mesh and
the dry-run lowers with ShapeDtypeStructs — one source of truth.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.launch import sharding as shlib
from repro.models import moe as moelib
from repro.models.transformer import forward
from repro.train import optimizer as opt_lib
from repro.train.losses import chunked_ce_loss


def make_train_step(cfg, opt_cfg: Optional[opt_lib.AdamWConfig] = None):
    """Microbatched (gradient-accumulation) train step.

    ``cfg.microbatches`` splits the global batch along dim 0 and scans,
    accumulating f32 grads.  This bounds the dominant training activation
    — the remat residual stack L x (B/k) x S x d — at the cost of k-fold
    smaller per-step matmuls.
    """
    opt_cfg = opt_cfg or opt_lib.AdamWConfig()

    def loss_fn(p, mb):
        out = forward(p, cfg, mb, mode="train")
        loss = chunked_ce_loss(p, cfg, out["hidden"], mb["labels"],
                               mb.get("mask"))
        return loss + out["aux"], (loss, out["aux"])

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def train_step(params, opt_state, batch):
        B = batch["tokens"].shape[0]
        k = max(1, min(cfg.microbatches, B))
        while B % k:
            k -= 1
        # keep B/k divisible by the data-parallel shard count: an uneven
        # microbatch (e.g. 256/16 = 16 rows on 32 dp shards) pads every
        # activation 2x per chip (measured on the 2x16x16 mesh, §Perf H3)
        ctx0 = shlib.current()
        if ctx0 is not None:
            dp = 1
            for ax in ("pod", "data"):
                if ax in ctx0.mesh.axis_names:
                    dp *= ctx0.mesh.shape[ax]
            while k > 1 and ((B // k) % dp or B % k):
                k -= 1
        # Hoisted MoE layout (§Perf): transform expert weights to the
        # shard-ready (M, r, d, f_lp) layout ONCE per step, differentiate
        # w.r.t. the transformed tree, and inverse-transform the grads —
        # instead of re-laying-out inside every (layer x microbatch)
        # iteration (the re-layout lowers to per-iteration collectives).
        ctx = shlib.current()
        hoist = (cfg.moe is not None and cfg.hoist_moe_layout
                 and ctx is not None and "model" in ctx.mesh.axis_names)
        M = ctx.mesh.shape["model"] if hoist else 1
        gparams = moelib.prepare_tree(params, cfg, M) if hoist else params
        if k == 1:
            (total, (loss, aux)), grads = grad_fn(gparams, batch)
        else:
            mbs = jax.tree_util.tree_map(
                lambda x: x.reshape((k, B // k) + x.shape[1:]), batch)

            def body(acc, mb):
                g_acc, tot_a, loss_a, aux_a = acc
                (tot, (loss, aux)), g = grad_fn(gparams, mb)
                g_acc = jax.tree_util.tree_map(
                    lambda a, b: a + b.astype(jnp.float32), g_acc, g)
                return (g_acc, tot_a + tot, loss_a + loss, aux_a + aux), None

            zeros = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), gparams)
            z = jnp.zeros((), jnp.float32)
            (grads, total, loss, aux), _ = jax.lax.scan(
                body, (zeros, z, z, z), mbs)
            grads = jax.tree_util.tree_map(lambda g: g / k, grads)
            total, loss, aux = total / k, loss / k, aux / k
        if hoist:
            grads = moelib.unprepare_grads(grads, cfg, M)
        params, opt_state, metrics = opt_lib.update(opt_cfg, grads,
                                                    opt_state, params)
        metrics.update({"loss": loss, "aux_loss": aux, "total_loss": total})
        return params, opt_state, metrics

    return train_step


def _maybe_hoist(cfg, params):
    ctx = shlib.current()
    if (cfg.moe is not None and cfg.hoist_moe_layout and ctx is not None
            and "model" in ctx.mesh.axis_names):
        return moelib.prepare_tree(params, cfg, ctx.mesh.shape["model"])
    return params


def make_prefill_step(cfg, max_len: Optional[int] = None):
    def prefill_step(params, batch):
        params = _maybe_hoist(cfg, params)
        out = forward(params, cfg, batch, mode="prefill", max_len=max_len)
        return out["last_logits"], out["states"]

    return prefill_step


def make_decode_step(cfg, sample: bool = False, temperature: float = 1.0):
    def decode_step(params, batch, states, rng=None):
        params = _maybe_hoist(cfg, params)
        out = forward(params, cfg, batch, mode="decode", states=states)
        logits = out["logits"]
        if sample:
            tok = jax.random.categorical(rng, logits / temperature, axis=-1)
        else:
            tok = jnp.argmax(logits, axis=-1)
        return logits, tok.astype(jnp.int32), out["states"]

    return decode_step


def make_paged_prefill_step(cfg):
    """One prompt chunk into the shared page pool (batch 1).

    batch = {tokens (1, C), start (), block_table (W,)}; returns
    (chunk_logits (1, C, V...), new page pools).  The engine calls this
    once per chunk with a fixed C so the jit cache stays single-entry.
    """
    def paged_prefill_step(params, batch, states):
        params = _maybe_hoist(cfg, params)
        out = forward(params, cfg, batch, mode="paged_prefill",
                      states=states)
        return out["chunk_logits"], out["states"]

    return paged_prefill_step


def make_paged_decode_step(cfg, sample: bool = False,
                           temperature: float = 1.0):
    """One decode token per lane over the shared page pool.

    batch = {tokens (B, 1), block_tables (B, W), lengths (B,)}; inactive
    lanes carry all-null tables and length 0, and their tokens are
    ignored by the engine.  Returns (logits, tokens, new page pools),
    and with expert layers also the round's tokens per expert (L_moe, E).
    """
    def paged_decode_step(params, batch, states, rng=None):
        params = _maybe_hoist(cfg, params)
        out = forward(params, cfg, batch, mode="paged_decode", states=states)
        logits = out["logits"]
        if sample:
            tok = jax.random.categorical(rng, logits / temperature, axis=-1)
        else:
            tok = jnp.argmax(logits, axis=-1)
        ret = (logits, tok.astype(jnp.int32), out["states"])
        if "expert_tokens" in out:
            ret += (out["expert_tokens"],)
        return ret

    return paged_decode_step


def make_eval_step(cfg):
    """Forward-only loss (validation)."""
    def eval_step(params, batch):
        out = forward(params, cfg, batch, mode="train")
        return chunked_ce_loss(params, cfg, out["hidden"], batch["labels"],
                               batch.get("mask"))

    return eval_step
