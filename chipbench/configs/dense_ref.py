"""Plain float32 reference of a dense decoder (Qwen3 or OLMo), exact or RM
attention.

It follows the published layer equations of the family the configuration's
``model_type`` names (hf ``Qwen3ForCausalLM``, ``OlmoForCausalLM``): token
embedding; per layer norm -> q/k/v projections -> (Qwen3: per-head RMSNorm
of q and k) -> rotary embedding (half rotation) -> causal grouped-query
attention -> output projection -> residual; norm -> SwiGLU MLP ->
residual; final norm; tied output head. Qwen3's norms are RMSNorms with a
scale; OLMo's are layer norms with no parameters. ``attention: "rm"``
replaces the softmax with the paper's Random-Maclaurin linear attention: q
and k, after the rotary embedding, are l2-normalised per head and scaled
by ``softplus(rm_scale)``, mapped through the random features of
exp(<q, k> / sigma2), and attention becomes
``(sum_s (zq.zk_s) v_s) / clamp(sum_s zq.zk_s)``, the clamp being
``sign(den) * max(|den|, eps)``.

It imports nothing of the program under test. The weights are drawn here,
from the seed, in names of this file's own; ``program_name`` says which of
them each leaf of the program's parameter tree is, and ``program_fields``
which settings of the program's model configuration the family's
published keys fix. The feature plan (how the feature budget splits over
degrees, and each degree's scale) is worked out here from the
configuration file, as ``FeaturePlan`` is defined in the paper (Kar &
Karnick 2012, Algorithm 1) with the stratified proportional allocation the
configuration names.

Every matrix product goes through ``mm``, so a control can round its
operands to a lower precision (``round_fn``).
"""
from __future__ import annotations

import math
from typing import Callable, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

Weights = Dict[str, jax.Array]
RoundFn = Optional[Callable[[jax.Array], jax.Array]]

# the program's parameter path (group key dropped) -> this file's name
_PROGRAM_LEAVES = {
    "embed/embedding": "embed",
    "final_norm/scale": "final_norm",
    "groups/norm1/scale": "attn_norm",
    "groups/attn/wq": "wq",
    "groups/attn/wk": "wk",
    "groups/attn/wv": "wv",
    "groups/attn/wo": "wo",
    "groups/attn/q_norm_scale": "q_norm",
    "groups/attn/k_norm_scale": "k_norm",
    "groups/attn/rm_est/omegas": "omegas",
    "groups/attn/rm_scale": "rm_scale",
    "groups/norm2/scale": "mlp_norm",
    "groups/mlp/w_gate": "w_gate",
    "groups/mlp/w_up": "w_up",
    "groups/mlp/w_down": "w_down",
}


# what each family fixes beyond the widths in its published config.json,
# and the key of its norms' eps (OLMo's file has none: its hf LayerNorm
# uses 1e-5)
FAMILIES = {
    "qwen3": {"norm_kind": "rmsnorm", "qk_norm": True,
              "eps_key": "rms_norm_eps"},
    "olmo": {"norm_kind": "nonparametric_ln", "qk_norm": False,
             "eps_key": "layer_norm_eps", "eps": 1e-5},
}


def _family(c: Dict) -> Dict:
    m = c["model"]
    fam = dict(FAMILIES[m["model_type"]])
    fam["eps"] = float(m.get(fam["eps_key"], fam.get("eps")))
    return fam


def _head_dim(m: Dict) -> int:
    return int(m.get("head_dim") or m["hidden_size"]
               // m["num_attention_heads"])


def program_fields(c: Dict) -> Dict:
    """The program's ``ModelConfig`` fields (dotted names) that the
    configuration's published keys fix, with their values."""
    m, fam = c["model"], _family(c)
    if m["hidden_act"] != "silu":
        raise NotImplementedError("the reference knows the SwiGLU MLP only")
    return {
        "num_layers": m["num_hidden_layers"], "d_model": m["hidden_size"],
        "num_heads": m["num_attention_heads"],
        "num_kv_heads": m["num_key_value_heads"],
        "resolved_head_dim": _head_dim(m), "d_ff": m["intermediate_size"],
        "vocab_size": m["vocab_size"], "rope_theta": m["rope_theta"],
        "norm_eps": fam["eps"], "tie_embeddings": m["tie_word_embeddings"],
        "qkv_bias": m["attention_bias"], "mlp_kind": "swiglu",
        "norm_kind": fam["norm_kind"], "qk_norm": fam["qk_norm"],
        "sliding_window": 0, "logits_softcap": 0.0,
    }


def program_name(path: Sequence[str]) -> str:
    """This file's name for one leaf of the program's parameter tree: the
    scanned group's own key (``b0_attn_mlp``) is not part of the name."""
    parts = [p for p in path if not (p.startswith("b") and "_" in p
                                     and p[1:p.index("_")].isdigit())]
    key = "/".join(parts)
    if key not in _PROGRAM_LEAVES:
        raise KeyError(f"program parameter {'/'.join(path)} has no "
                       "counterpart in the reference")
    return _PROGRAM_LEAVES[key]


# ---------------------------------------------------------------------------
# the RM feature plan
# ---------------------------------------------------------------------------
def rm_plan(rm: Dict) -> Dict:
    """Degrees, feature counts and scales of the RM map of exp(<x,y>/s2).

    Maclaurin coefficients a_n = s2^-n / n!; measure q_n ~ a_n R^2n
    (``proportional``, R = ``qk_scale``); stratified counts
    c_n = round(D q_n) by largest remainder; scale_n = sqrt(a_n / c_n).
    The c_0 constant features collapse into one column sqrt(a_0).
    """
    if rm["measure"] != "proportional" or not rm["stratified"]:
        raise NotImplementedError("the reference knows the stratified "
                                  "proportional plan only")
    n_max, d_feat = int(rm["n_max"]), int(rm["num_features"])
    coefs = np.asarray([math.exp(-n * math.log(rm["sigma2"])
                                 - math.lgamma(n + 1))
                        for n in range(n_max + 1)])
    q = coefs * (float(rm["qk_scale"]) ** 2) ** np.arange(n_max + 1)
    q = np.where(coefs > 0, q, 0.0)
    q = q / q.sum()
    raw = q * d_feat
    counts = np.floor(raw).astype(np.int64)
    deficit = d_feat - int(counts.sum())
    if deficit > 0:
        counts[np.argsort(-(raw - counts))[:deficit]] += 1
    scales = np.where(counts > 0, np.sqrt(coefs / np.maximum(counts, 1)),
                      0.0)
    degrees = [n for n in range(1, n_max + 1) if counts[n]]
    const = float(np.sqrt(counts[0]) * scales[0]) if counts[0] else 0.0
    return {
        "degrees": degrees,
        "counts": [int(counts[n]) for n in degrees],
        "scales": [float(scales[n]) for n in degrees],
        "const": const,
        "total_rows": int(sum(int(counts[n]) * n for n in degrees)),
        "output_dim": int((1 if const else 0)
                          + sum(int(counts[n]) for n in degrees)),
    }


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------
def shapes(c: Dict) -> Dict[str, tuple]:
    """This file's weight names and shapes for configuration ``c``."""
    m, fam = c["model"], _family(c)
    d, h, kv, dh = (m["hidden_size"], m["num_attention_heads"],
                    m["num_key_value_heads"], _head_dim(m))
    n_layers, ff, vocab = (m["num_hidden_layers"], m["intermediate_size"],
                           m["vocab_size"])
    out = {
        "embed": (vocab, d),
        "wq": (n_layers, d, h * dh), "wk": (n_layers, d, kv * dh),
        "wv": (n_layers, d, kv * dh), "wo": (n_layers, h * dh, d),
        "w_gate": (n_layers, d, ff), "w_up": (n_layers, d, ff),
        "w_down": (n_layers, ff, d),
    }
    if fam["norm_kind"] == "rmsnorm":
        out.update(final_norm=(d,), attn_norm=(n_layers, d),
                   mlp_norm=(n_layers, d))
    if fam["qk_norm"]:
        out.update(q_norm=(n_layers, dh), k_norm=(n_layers, dh))
    if c["attention"] == "rm":
        out["omegas"] = (n_layers, rm_plan(c["rm"])["total_rows"], dh)
        out["rm_scale"] = (n_layers,)
    return out


def init(c: Dict, key: jax.Array) -> Weights:
    """Weights from ``key`` in float32: norm scales 1, ``rm_scale`` the
    inverse softplus of ``qk_scale``, RM omegas Rademacher +-1, every
    other matrix normal with standard deviation ``initializer_range``.
    Traceable: the caller jits it, so the weights are made on the
    device."""
    std = float(c["model"]["initializer_range"])
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes(c).items())):
        k = jax.random.fold_in(key, i)
        if name.endswith("norm"):
            out[name] = jnp.ones(shape, jnp.float32)
        elif name == "rm_scale":
            out[name] = jnp.full(shape, math.log(math.expm1(
                float(c["rm"]["qk_scale"]))), jnp.float32)
        elif name == "omegas":
            out[name] = jnp.where(jax.random.bernoulli(k, 0.5, shape),
                                  1.0, -1.0).astype(jnp.float32)
        else:
            out[name] = std * jax.random.normal(k, shape, jnp.float32)
    return out


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def mm(a: jax.Array, b: jax.Array, spec: str, round_fn: RoundFn = None):
    if round_fn is not None:
        a, b = round_fn(a), round_fn(b)
    return jnp.einsum(spec, a, b, preferred_element_type=jnp.float32)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _norm(x, w, name, eps):
    """The family's norm: RMSNorm with this file's scale ``name`` where it
    has one, else a layer norm with no parameters (OLMo)."""
    if name in w:
        return _rms_norm(x, w[name], eps)
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps)


def _rope(x, pos, theta):
    """x [T, H, dh]; rotate the two halves (Qwen3 / llama layout)."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos[:, None, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _features(x, omegas, plan, round_fn):
    """x [T, H, dh] -> RM features [T, H, F]."""
    proj = mm(x, omegas, "thd,rd->thr", round_fn)
    cols = []
    if plan["const"]:
        cols.append(jnp.full(x.shape[:2] + (1,), plan["const"], jnp.float32))
    off = 0
    for n, cnt, sc in zip(plan["degrees"], plan["counts"], plan["scales"]):
        blk = proj[..., off:off + cnt * n].reshape(x.shape[:2] + (cnt, n))
        cols.append(jnp.prod(blk, axis=-1) * sc)
        off += cnt * n
    return jnp.concatenate(cols, -1)


def _attention(c, lw, q, k, v, round_fn):
    """q [T, h, dh], k/v [T, kv, dh] -> [T, h, dh], causal."""
    t, h, dh = q.shape
    rep = h // k.shape[1]
    causal = jnp.tril(jnp.ones((t, t), bool))
    if c["attention"] == "rm":
        rm = c["rm"]
        plan = rm_plan(rm)
        sc = jax.nn.softplus(lw["rm_scale"])

        def unit(x):
            n = jnp.sqrt(jnp.sum(x * x, -1, keepdims=True))
            return x / jnp.maximum(n, 1e-6) * sc

        zq = _features(unit(q), lw["omegas"], plan, round_fn)
        zk = jnp.repeat(_features(unit(k), lw["omegas"], plan, round_fn),
                        rep, axis=1)
        w = jnp.where(causal[None], mm(zq, zk, "thf,shf->hts", round_fn),
                      0.0)
        num = mm(w, jnp.repeat(v, rep, axis=1), "hts,shd->thd", round_fn)
        den = jnp.sum(w, -1).T[..., None]                    # [T, h, 1]
        eps = float(rm["eps"])
        den = jnp.where(jnp.abs(den) < eps, jnp.where(den >= 0, eps, -eps),
                        den)
        return num / den
    s = mm(q, jnp.repeat(k, rep, axis=1), "thd,shd->hts", round_fn)
    s = jnp.where(causal[None], s / math.sqrt(dh), -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return mm(p, jnp.repeat(v, rep, axis=1), "hts,shd->thd", round_fn)


def hidden(c: Dict, w: Weights, tokens: jax.Array,
           round_fn: RoundFn = None) -> jax.Array:
    """Final-normed hidden states [T, d] of one causal sequence [T]."""
    m, fam = c["model"], _family(c)
    h, kv, dh = (m["num_attention_heads"], m["num_key_value_heads"],
                 _head_dim(m))
    eps, theta = fam["eps"], float(m["rope_theta"])
    t = tokens.shape[0]
    pos = jnp.arange(t)
    x = w["embed"][tokens]
    per_layer = {k: v for k, v in w.items()
                 if k not in ("embed", "final_norm")}

    def layer(x, lw):
        a = _norm(x, lw, "attn_norm", eps)
        q = mm(a, lw["wq"], "td,de->te", round_fn).reshape(t, h, dh)
        k = mm(a, lw["wk"], "td,de->te", round_fn).reshape(t, kv, dh)
        v = mm(a, lw["wv"], "td,de->te", round_fn).reshape(t, kv, dh)
        if fam["qk_norm"]:
            q = _rms_norm(q, lw["q_norm"], eps)
            k = _rms_norm(k, lw["k_norm"], eps)
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        o = _attention(c, lw, q, k, v, round_fn).reshape(t, h * dh)
        x = x + mm(o, lw["wo"], "te,ed->td", round_fn)
        a = _norm(x, lw, "mlp_norm", eps)
        g = mm(a, lw["w_gate"], "td,df->tf", round_fn)
        u = mm(a, lw["w_up"], "td,df->tf", round_fn)
        x = x + mm(jax.nn.silu(g) * u, lw["w_down"], "tf,fd->td", round_fn)
        return x, None

    x, _ = jax.lax.scan(layer, x, per_layer)
    return _norm(x, w, "final_norm", eps)


def logits_at(w: Weights, hid: jax.Array, idx: jax.Array,
              round_fn: RoundFn = None) -> jax.Array:
    """Logits [N, V] at positions ``idx`` [N] of the hidden states, through
    the tied output head."""
    return mm(hid[idx], w["embed"], "nd,vd->nv", round_fn)
