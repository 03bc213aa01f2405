"""The RM featurize kernel of one decode step, one launch (one layer): the
new query of each busy lane (per head) and its new key (per KV head) mapped
to features, at 2 bytes a value: read the rows and the omegas once, write
the features. Idle lanes are padding and are not counted.
"""
from __future__ import annotations

from typing import Dict

BYTES = 2


def flops(c: Dict, lanes: int) -> float:
    from harness import loader

    m, fwd = c["model"], loader.cost("model_forward")
    rows, _ = fwd.rm_sizes(c)
    vectors = lanes * (m["num_attention_heads"] + m["num_key_value_heads"])
    return 2.0 * vectors * rows * fwd.head_dim(m)


def bytes_moved(c: Dict, lanes: int) -> float:
    from harness import loader

    m, fwd = c["model"], loader.cost("model_forward")
    rows, feats = fwd.rm_sizes(c)
    dh = fwd.head_dim(m)
    vectors = lanes * (m["num_attention_heads"] + m["num_key_value_heads"])
    return BYTES * (vectors * (dh + feats) + rows * dh)
