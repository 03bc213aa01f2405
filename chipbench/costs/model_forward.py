"""FLOPs of tokens through a dense decoder with grouped-query attention,
exact or RM, counted from the configuration's shapes as the algorithm needs
them: matrix products at 2 FLOPs a multiply-add, no padding, keys and
values and RM state per KV head, RM features as their projections, and the
output head only at the position that is sampled.
"""
from __future__ import annotations

from typing import Dict, Iterable


def head_dim(m: Dict) -> int:
    """The head size; a published config without ``head_dim`` (OLMo) has
    hidden / heads."""
    return int(m.get("head_dim") or m["hidden_size"]
               // m["num_attention_heads"])


def _dims(c: Dict):
    m = c["model"]
    return (m["hidden_size"], m["num_attention_heads"],
            m["num_key_value_heads"], head_dim(m),
            m["intermediate_size"], m["num_hidden_layers"], m["vocab_size"])


def rm_sizes(c: Dict):
    """(total omega rows R, feature columns F) of the RM map."""
    from harness import loader

    plan = loader.reference(c).rm_plan(c["rm"])
    return plan["total_rows"], plan["output_dim"]


def dense_per_token(c: Dict) -> float:
    """Projections and MLP of one token, all layers."""
    d, h, kv, dh, ff, n_layers, _ = _dims(c)
    return 2.0 * n_layers * (d * h * dh + 2 * d * kv * dh + h * dh * d
                             + 3 * d * ff)


def rm_attention_per_token(c: Dict) -> float:
    """Featurize q (per head) and k (per KV head), fold k and v into the
    state, read the query's output and normaliser: one token, one layer."""
    _, h, kv, dh, _, _, _ = _dims(c)
    rows, feats = rm_sizes(c)
    return (2.0 * rows * dh * (h + kv) + kv * (2.0 * feats * dh + feats)
            + h * (2.0 * feats * dh + 2.0 * feats))


def attention_per_token(c: Dict, keys: int) -> float:
    """Attention of one query token over ``keys`` positions, all layers."""
    _, h, _, dh, _, n_layers, _ = _dims(c)
    if c["attention"] == "rm":
        return n_layers * rm_attention_per_token(c)
    return n_layers * 4.0 * h * dh * keys


def head(c: Dict) -> float:
    """The output head at one position."""
    d, *_, vocab = _dims(c)
    return 2.0 * d * vocab


def prefill(c: Dict, tokens: int) -> float:
    """A prompt of ``tokens`` real tokens, causal, one sampled position."""
    d, h, _, dh, _, n_layers, _ = _dims(c)
    if c["attention"] == "rm":
        attn = tokens * attention_per_token(c, 0)
    else:
        attn = n_layers * 2.0 * h * dh * tokens * (tokens + 1)
    return tokens * dense_per_token(c) + attn + head(c)


def decode(c: Dict, contexts: Iterable[int]) -> float:
    """One decode step of the busy lanes, lane ``i`` attending over
    ``contexts[i]`` positions (its new token included)."""
    return sum(dense_per_token(c) + attention_per_token(c, n) + head(c)
               for n in contexts)
