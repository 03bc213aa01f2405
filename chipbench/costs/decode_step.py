"""Bytes one decode step must move at the configuration's compute precision
(2 bytes a value): every parameter read once (the tied output head reads
the embedding table once; the RM omegas are parameters), and the attention
state of the busy lanes: the live keys and values read and the new ones
written (exact), or each KV head's RM state (S, n) read and written (rm).
Activations are left out.
"""
from __future__ import annotations

from typing import Dict, Iterable

BYTES = 2


def parameters(c: Dict) -> float:
    """Every weight of the configuration's reference, once."""
    import math

    from harness import loader

    return BYTES * sum(math.prod(shape) for shape
                       in loader.reference(c).shapes(c).values())


def state(c: Dict, contexts: Iterable[int]) -> float:
    """Attention state of the busy lanes, lane ``i`` at ``contexts[i]``
    positions: the exact cache's keys and values (the new ones written,
    the older ones read), or the RM state read and written back."""
    from harness import loader

    m = c["model"]
    kv, dh, n_layers = (m["num_key_value_heads"],
                        loader.cost("model_forward").head_dim(m),
                        m["num_hidden_layers"])
    contexts = list(contexts)
    if c["attention"] == "rm":
        _, feats = loader.cost("model_forward").rm_sizes(c)
        per_lane = 2 * kv * (feats * dh + feats)
        return n_layers * BYTES * per_lane * len(contexts)
    return n_layers * BYTES * 2 * kv * dh * sum(contexts)


def step(c: Dict, contexts: Iterable[int]) -> float:
    """All bytes of one step."""
    contexts = list(contexts)
    return parameters(c) + state(c, contexts)
