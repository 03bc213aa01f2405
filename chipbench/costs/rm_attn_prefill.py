"""The fused causal RM attention kernel of prefill, one launch (one layer)
over a prompt of ``tokens`` real tokens, counted as the algorithm needs it
at 2 bytes a value: featurize q (per head) and k (per KV head), fold k and
v into the running state and read each query's output and normaliser
(``model_forward.rm_attention_per_token``); read q, k, v and the omegas
once, write the output and the final state (S, n) of each KV head.
Padding to the bucket is not counted.
"""
from __future__ import annotations

from typing import Dict

BYTES = 2


def flops(c: Dict, tokens: int) -> float:
    from harness import loader

    return tokens * loader.cost("model_forward").rm_attention_per_token(c)


def bytes_moved(c: Dict, tokens: int) -> float:
    from harness import loader

    m, fwd = c["model"], loader.cost("model_forward")
    h, kv, dh = (m["num_attention_heads"], m["num_key_value_heads"],
                 fwd.head_dim(m))
    rows, feats = fwd.rm_sizes(c)
    return BYTES * (tokens * dh * (h + 2 * kv) + rows * dh
                    + tokens * h * dh + kv * (feats * dh + feats))
