"""Plain float32 reference of a sparse-expert decoder whose attention
layers are windowed or full by ``layer_types`` (Mellum2).

Written from the configuration file alone; it imports nothing of the
program (only the helpers of ``dense_gqa.py``: weight draws, float8
rounding, norms, the head and the gaps).  The weights are made here from
the engine's seed exactly as the program draws them (per layer, the same
splits of the same key; attention, then the router in float32, then the
experts' up, down and gate matrices, one key each), held in float32.
The forward pass:

    x = E[tokens]
    per layer i, kind = layer_types[i]:
        h = rmsnorm(x);  q, k, v = h Wq, h Wk, h Wv
        rotary on q, k with rope_parameters[kind]: plain, or YaRN
            (ramped inverse frequencies, cos and sin times
            attention_factor), split halves
        x += softmax(q k^T / sqrt(hd), causal; on sliding layers only
             the last sliding_window keys) v Wo     (GQA groups)
        h = rmsnorm(x);  p = softmax(h Wr);  top num_experts_per_tok,
            renormalised (norm_topk_prob)
        x += sum over the chosen experts e of p_e silu(h Wg_e) * (h Wu_e) Wd_e
    logits = rmsnorm(x) W_head

No token is dropped: every expert is evaluated on every token and
weighted by its (zero unless chosen) gate.  Every product runs at
``Precision.HIGHEST``; attention runs in blocks of queries so that a
sequence of ``max_len`` fits beside nothing on one chip.  With
``quant=True`` the same pass is the control: every matrix product takes
its operands rounded to float8 e4m3 (``dense_gqa._mm``), the precision
below the served bfloat16.
"""
from __future__ import annotations

import functools
import json
import math
import sys
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import dense_gqa as dg

HIGHEST = dg.HIGHEST
Q_BLOCK = 512                  # queries per attention block


def _check(c: dict) -> None:
    dg._eps(c)
    if not dg._gated(c):
        raise ValueError("no reference for non-gated experts")
    if not c.get("norm_topk_prob", False):
        raise ValueError("no reference for unrenormalised top-k gates")
    if any(t != "sparse" for t in c["mlp_layer_types"]):
        raise ValueError("no reference for dense FFN layers")


# ---------------------------------------------------------------------------
# rotary tables
# ---------------------------------------------------------------------------


def inv_freq(rp: dict, hd: int) -> Tuple[np.ndarray, float]:
    """Inverse frequencies (hd/2,) and the cos/sin scale of one
    ``rope_parameters`` entry: plain, or YaRN as HF computes it."""
    theta = float(rp["rope_theta"])
    pos = theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd)
    if rp["rope_type"] == "default":
        return (1.0 / pos).astype(np.float32), 1.0
    if rp["rope_type"] != "yarn":
        raise ValueError(f"no reference for rope_type {rp['rope_type']!r}")
    orig = rp["original_max_position_embeddings"]

    def dim_of(rotations):
        return (hd * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(dim_of(rp["beta_fast"])), 0)
    high = min(math.ceil(dim_of(rp["beta_slow"])), hd - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(hd // 2, dtype=np.float32) - low)
                   / (high - low), 0.0, 1.0)
    extrapolate = 1.0 - ramp
    inv = ((1.0 / (rp["factor"] * pos)) * (1.0 - extrapolate)
           + (1.0 / pos) * extrapolate)
    return inv.astype(np.float32), float(rp["attention_factor"])


def _rope_table(rp: dict, hd: int, T: int):
    inv, scale = inv_freq(rp, hd)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None, None] * jnp.asarray(inv)
    return jnp.cos(ang) * scale, jnp.sin(ang) * scale


def _rot(x, table):
    cos, sin = table
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnums=(1,))
def _layer_params(key, shape: Tuple) -> Dict[str, jax.Array]:
    d, H, KV, hd, E, f, dtype = shape
    ks = jax.random.split(key, 3)
    ka = jax.random.split(ks[0], 4)
    km = jax.random.split(ks[1], 4)

    def experts(k, fan_in, fan_out):
        return jax.vmap(lambda ke: dg._dense(ke, fan_in, fan_out, dtype))(
            jax.random.split(k, E))

    return {"wq": dg._dense(ka[0], d, H * hd, dtype),
            "wk": dg._dense(ka[1], d, KV * hd, dtype),
            "wv": dg._dense(ka[2], d, KV * hd, dtype),
            "wo": dg._dense(ka[3], H * hd, d, dtype),
            "router": dg._dense(km[0], d, E, jnp.float32),
            "we_up": experts(km[1], d, f),
            "we_down": experts(km[2], f, d),
            "we_gate": experts(km[3], d, f),
            "norm1": jnp.ones((d,), jnp.float32),
            "norm2": jnp.ones((d,), jnp.float32)}


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnums=(3,))
def _layer(p, x, table, dims: Tuple):
    """One decoder layer over one sequence x (T, d)."""
    H, KV, hd, eps, window, top_k, quant = dims
    T = x.shape[0]
    h = dg._rms(x, p["norm1"], eps)
    q = _rot(dg._mm(h, p["wq"], quant).reshape(T, H, hd), table)
    k = _rot(dg._mm(h, p["wk"], quant).reshape(T, KV, hd), table)
    v = dg._mm(h, p["wv"], quant).reshape(T, KV, hd)
    if quant:
        q, k, v = dg._q8(q, -1), dg._q8(k, -1), dg._q8(v, -1)
    G = H // KV
    qg = q.reshape(T, KV, G, hd)
    cq = math.gcd(T, Q_BLOCK)
    kpos = jnp.arange(T)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(qg, i * cq, cq, axis=0)
        s = jnp.einsum("qkgd,ckd->kgqc", qb, k, precision=HIGHEST)
        s = s / math.sqrt(hd)
        qpos = i * cq + jnp.arange(cq)
        mask = kpos[None, :] <= qpos[:, None]
        if window is not None:
            mask &= kpos[None, :] > qpos[:, None] - window
        w = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        if quant:
            w = dg._q8(w, -1)
        return jnp.einsum("kgqc,ckd->qkgd", w, v, precision=HIGHEST)

    o = jax.lax.map(block, jnp.arange(T // cq)).reshape(T, H * hd)
    x = x + dg._mm(o, p["wo"], quant)

    h = dg._rms(x, p["norm2"], eps)
    probs = jax.nn.softmax(dg._mm(h, p["router"], quant), axis=-1)
    gate, idx = jax.lax.top_k(probs, top_k)
    gate = gate / gate.sum(-1, keepdims=True)
    E = probs.shape[-1]
    weight = jnp.einsum("tk,tke->te", gate, jax.nn.one_hot(idx, E),
                        precision=HIGHEST)             # (T, E)

    def expert(acc, e):
        a = (jax.nn.silu(dg._mm(h, p["we_gate"][e], quant))
             * dg._mm(h, p["we_up"][e], quant))
        return acc + weight[:, e, None] * dg._mm(a, p["we_down"][e],
                                                 quant), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(E))
    return x + y


def _forward(c: dict, seed: int, toks: List[np.ndarray], modes):
    """Final hidden states of each padded token row, per mode (False:
    the reference, True: the float8 control), and the output weights."""
    _check(c)
    L, d, H, KV, hd = dg._dims(c)
    dtype = jnp.dtype(c["torch_dtype"])
    E = c["num_experts"]
    shape = (d, H, KV, hd, E, c["moe_intermediate_size"], dtype)
    eps = dg._eps(c)
    keys = jax.random.split(jax.random.key(seed), L + 4)
    out = dg._out_params(keys, d, c["vocab_size"],
                         (bool(c["tie_word_embeddings"]), dtype))
    T = toks[0].size
    tables = {kind: _rope_table(rp, hd, T)
              for kind, rp in c["rope_parameters"].items()}
    xs = {}
    for m in modes:
        emb = dict(out, embed=dg._q8(out["embed"], -1)) if m else out
        xs[m] = [dg._embed(emb, jnp.asarray(t)) for t in toks]
    for i, kind in enumerate(c["layer_types"][:L]):
        window = (int(c["sliding_window"]) if kind == "sliding_attention"
                  else None)
        p = _layer_params(keys[i], shape)
        for m in modes:
            dims = (H, KV, hd, eps, window, c["num_experts_per_tok"], m)
            xs[m] = [_layer(p, x, tables[kind], dims) for x in xs[m]]
        del p
    return out, xs


def logits(c: dict, seed: int, tokens: np.ndarray, pad_to: int, start: int,
           rows: int) -> np.ndarray:
    """Reference logits (rows, V) at positions start..start+rows-1 of one
    token sequence."""
    t = np.zeros(pad_to, np.int32)
    t[:tokens.size] = tokens
    out, xs = _forward(c, seed, [t], (False,))
    return np.asarray(dg._head_rows(out, xs[False][0], jnp.int32(start),
                                    rows, (dg._eps(c), False)))


def token_gaps(c: dict, seed: int, seqs: Sequence[Tuple[np.ndarray,
                                                         np.ndarray]],
               pad_to: int, rows: int, control: bool = False
               ) -> Dict[str, List[np.ndarray]]:
    """Reference gaps of served tokens, for one engine's weights, token
    by token: ``dense_gqa.served_gaps``'s contract."""
    eps = dg._eps(c)
    toks, starts = [], []
    for prompt, served in seqs:
        if len(served) > rows or len(prompt) + len(served) - 1 > pad_to:
            raise ValueError("sequence longer than the reference's pad")
        t = np.zeros(pad_to, np.int32)
        full = np.concatenate([prompt, served[:-1]])
        t[:full.size] = full
        toks.append(t)
        starts.append(len(prompt) - 1)
    out, xs = _forward(c, seed, toks, (False, True) if control else (False,))
    res: Dict[str, List[np.ndarray]] = {"served": []}
    if control:
        res["control"] = []
    for j, (_, served) in enumerate(seqs):
        n = len(served)
        start = min(starts[j], pad_to - rows)
        off = starts[j] - start
        target = np.zeros(rows, np.int32)
        target[off:off + n] = served
        ref = dg._head_rows(out, xs[False][j], jnp.int32(start), rows,
                            (eps, False))
        gap = np.asarray(dg._gap_of(ref, jnp.asarray(target)))
        res["served"].append(gap[off:off + n])
        if control:
            ctl = dg._head_rows(out, xs[True][j], jnp.int32(start), rows,
                                (eps, True))
            gap = np.asarray(dg._control_gap(ref, ctl))
            res["control"].append(gap[off:off + n])
    return res


def served_gaps(c: dict, seed: int, seqs: Sequence[Tuple[np.ndarray,
                                                          np.ndarray]],
                pad_to: int, rows: int, control: bool = False
                ) -> Dict[str, List[np.ndarray]]:
    """The numbers compared: per sequence, one, the mean of its served
    tokens' gaps (``token_gaps``); with ``control``, the same mean of
    the gaps of the float8 control's first choices.

    Not the widest gap, as for a dense model.  Where a token's K-th and
    (K+1)-th router logits lie closer than bf16 rounding moves them (the
    closest such pair of a Mellum2 token over its 12 layers is 0.004
    apart at the median), the bf16 program may route it to another
    expert than the float32 reference.  A sound run then serves a token
    below the reference's best at about one position in ten, some by a
    few tenths, and its widest gap comes within 1.5x of the control's;
    the mean over a request's tokens stays over 3x under the control's
    (PERF.md).  The harness reports the largest of these
    means as ``max_logit_gap``; the widest token gaps are logged.
    """
    res = token_gaps(c, seed, seqs, pad_to, rows, control)
    widest = {k: max(float(g.max()) for g in v) for k, v in res.items()}
    print(f"[moe_swa] widest token gap {json.dumps(widest)}",
          file=sys.stderr, flush=True)
    return {k: [np.full(1, g.mean()) for g in v] for k, v in res.items()}
