"""Operations and bytes of one decode round of a sparse-expert decoder
whose attention layers are windowed or full by ``layer_types`` (Mellum2),
from its configuration file and the keys each live lane attends: the
yardstick for ``moe_decode_roofline.tpot`` and
``paged_decode_roofline.window``.

What the algorithm needs, never how the program does it: the lanes
that decode (not the idle ones a round carries), the experts T lanes
are expected to reach when routing is uniform, E (1 - (1 - K/E)^T), and
per layer the live keys, min(length, window) on a windowed layer.

Uniform routing is an upper bound on the experts a round reaches: where
tokens route skewed, fewer distinct experts take them.  At the
benchmark's seeded random weights Mellum2's 16 routed rows reach about
32 experts a layer, not 56.4, so a share over these bytes reads high,
and a kernel that read only the experts reached could read over 100%.
The run record carries no routing counts to count by instead.
"""
from __future__ import annotations

from typing import List, Sequence, Tuple

from bench.flops import DTYPE_BYTES

ROUTER_BYTES = 4                 # the router's weights are float32


def _dims(c: dict):
    return (c["num_hidden_layers"], c["hidden_size"],
            c["num_attention_heads"], c["num_key_value_heads"],
            c["head_dim"], c["vocab_size"], c["num_experts"],
            c["num_experts_per_tok"], c["moe_intermediate_size"])


def windows(c: dict) -> List[int]:
    """Per layer, the keys a query may reach (0: no window)."""
    L = c["num_hidden_layers"]
    return [int(c["sliding_window"]) if k == "sliding_attention" else 0
            for k in c["layer_types"][:L]]


def expected_experts(c: dict, lanes: int) -> float:
    """Distinct experts ``lanes`` tokens reach per layer under uniform
    top-K routing over E experts."""
    E, K = c["num_experts"], c["num_experts_per_tok"]
    return E * (1.0 - (1.0 - K / E) ** lanes)


def attention_layers(c: dict, lengths: Sequence[int]
                     ) -> List[Tuple[int, int]]:
    """(FLOPs, bytes) of each layer's paged decode attention for one
    round: QK^T and PV over the live keys, each read once, plus each
    lane's query and output."""
    _, _, H, KV, hd, _, _, _, _ = _dims(c)
    b = DTYPE_BYTES[c["torch_dtype"]]
    out = []
    for w in windows(c):
        keys = sum(min(n, w) if w else n for n in lengths)
        out.append((4 * H * hd * keys,
                    keys * KV * hd * 2 * b + len(lengths) * H * hd * 2 * b))
    return out


def decode_round(c: dict, lengths: Sequence[int]) -> Tuple[float, float]:
    """(FLOPs, bytes) of one decode round of ``len(lengths)`` lanes:
    every layer's attention weights, router and expected experts read
    once, the head once, and the live KV."""
    L, d, H, KV, hd, V, E, K, f = _dims(c)
    b = DTYPE_BYTES[c["torch_dtype"]]
    T = len(lengths)
    attn = d * H * hd + 2 * d * KV * hd + H * hd * d
    expert = 3 * d * f
    flops = 2 * T * (L * (attn + d * E + K * expert) + d * V)
    nbytes = (b * (L * attn + d * V) + ROUTER_BYTES * L * d * E
              + b * L * expected_experts(c, T) * expert)
    for f_, b_ in attention_layers(c, lengths):
        flops += f_
        nbytes += b_
    return float(flops), float(nbytes)
