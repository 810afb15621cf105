"""Operations and bytes the algorithm needs, from a configuration file's
shapes: the yardstick for ``step_mfu.*`` and ``paged_decode_roofline.*``.

The counts depend only on the configuration and on the live context
lengths, never on how the program implements a step: padded pages,
masked lanes and recomputation are not counted.
"""
from __future__ import annotations

from typing import Iterable

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def _dims(c: dict):
    return (c["num_hidden_layers"], c["hidden_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["head_dim"], c["intermediate_size"],
            c["vocab_size"])


def matmul_params(c: dict) -> int:
    """Weights that multiply every token: per layer the Q/K/V/O
    projections and the MLP (three matrices gated, two plain), plus the
    output head (tied or not, it is a d x V product)."""
    L, d, H, KV, hd, f, V = _dims(c)
    mlp = (3 if c["hidden_act"] == "silu" else 2) * d * f
    attn = d * H * hd + 2 * d * KV * hd + H * hd * d
    return L * (attn + mlp) + d * V


def token_flops(c: dict, context: int) -> int:
    """Model FLOPs for one token that attends ``context`` keys (itself
    included): 2 per weight, plus QK^T and PV over the live context."""
    L, _, H, _, hd, _, _ = _dims(c)
    return 2 * matmul_params(c) + 4 * L * H * hd * context


def prefill_flops(c: dict, start: int, n: int) -> int:
    """FLOPs of prefilling positions start..start+n-1 (causal: position p
    attends p + 1 keys)."""
    L, _, H, _, hd, _, _ = _dims(c)
    ctx = n * start + n * (n + 1) // 2
    return 2 * matmul_params(c) * n + 4 * L * H * hd * ctx


def decode_attention(c: dict, lengths: Iterable[int]):
    """(FLOPs, bytes) of ONE layer's paged decode attention for the lanes
    of one call, ``lengths`` being the keys each live lane attends:
    QK^T and PV, and reading each live key and value once plus each
    lane's query and output."""
    _, _, H, KV, hd, _, _ = _dims(c)
    b = DTYPE_BYTES[c["torch_dtype"]]
    lengths = list(lengths)
    keys = sum(lengths)
    flops = 4 * H * hd * keys
    nbytes = keys * KV * hd * 2 * b + len(lengths) * H * hd * 2 * b
    return flops, nbytes
