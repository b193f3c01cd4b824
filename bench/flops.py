"""Work that a served step needs, counted from what the requests need:
the tokens each slot fed and the context each attended, as the harness
recorded them. Nothing here reads the kernel's grid or its padded table,
so a kernel that stops reading padding, or one with another grid, is read
against the same yardstick.

``model`` is a configuration file's ``model`` block (Hugging Face key
names); ``fed`` is a step's list of ``(position before, tokens fed)``, one
pair a slot.
"""
from __future__ import annotations

from typing import Dict, Iterable, Tuple

BF16 = 2


def dims(model: Dict) -> Tuple[int, int, int, int, int, int]:
    d = model["hidden_size"]
    H = model["num_attention_heads"]
    return (d, H, model["num_key_value_heads"], d // H,
            model["intermediate_size"], model["vocab_size"])


def per_layer_matmul_params(model: Dict) -> int:
    """Weights a token multiplies in one dense SwiGLU layer: q, k, v and o
    projections and the gated MLP. A copy of the dense branch of
    ``benchmarks/roofline.py:_per_layer_matmul_params``."""
    d, H, KV, Dh, f, _ = dims(model)
    attn = d * H * Dh + 2 * d * KV * Dh + H * Dh * d
    return attn + 2 * d * f + f * d


def attended_keys(fed: Iterable[Tuple[int, int]]) -> int:
    """Query-key pairs: fed token j of a slot at position p attends the
    p + j + 1 positions up to and including its own."""
    return sum(n * p + n * (n + 1) // 2 for p, n in fed)


def step_flops(model: Dict, layers: int, fed) -> float:
    """Model FLOPs of one step: every fed token through every layer's
    matmuls, attention over the real context, and the output head for
    the one position a slot emits from."""
    d, H, _, Dh, _, V = dims(model)
    fed = list(fed)
    tokens = sum(n for _, n in fed)
    mm = 2.0 * tokens * layers * per_layer_matmul_params(model)
    attn = 4.0 * H * Dh * attended_keys(fed) * layers
    head = 2.0 * d * V * len(fed)
    return mm + attn + head


def paged_attention_work(model: Dict, layers: int, fed) -> Tuple[float,
                                                                  float]:
    """(FLOPs, bytes) the paged attention kernel needs for one step:
    q.k and p.v over the real context of each fed token, and one read of
    each slot's K and V rows up to its last fed position, its queries
    read and its outputs written, in bfloat16."""
    _, H, KV, Dh, _, _ = dims(model)
    fed = list(fed)
    flops = 4.0 * H * Dh * attended_keys(fed) * layers
    kv = sum(p + n for p, n in fed) * KV * Dh * BF16 * 2
    qo = sum(n for _, n in fed) * H * Dh * BF16 * 2
    return flops, float((kv + qo) * layers)


def roofline_seconds(flops: float, nbytes: float, peaks: Dict
                     ) -> Tuple[float, str]:
    """The least time the chip could take, and which peak bounds it."""
    tc = flops / peaks["bf16_flops"]
    tm = nbytes / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
