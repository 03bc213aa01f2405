"""Roofline-term derivation from compiled XLA artifacts (no hardware).

Per (arch x shape x mesh) the dry-run produces a compiled SPMD program; from
it we derive the three roofline terms (EXPERIMENTS.md §Roofline):

    compute    = HLO_FLOPs_per_device / peak_FLOPs_per_chip
    memory     = HLO_bytes_per_device / HBM_bw_per_chip
    collective = sum(collective payload bytes per device) / link_bw

Notes on sources:
  * ``compiled.cost_analysis()`` reports per-device FLOPs/bytes for the SPMD
    partitioned module (shapes in the HLO are shard shapes).
  * collective bytes are NOT in cost_analysis: we parse the post-optimization
    HLO text and sum RESULT-shape bytes of every all-reduce / all-gather /
    reduce-scatter / all-to-all / collective-permute (result-shape ==
    received payload per device; all-reduce counted twice — reduce-scatter +
    all-gather phases of a ring).
  * Hardware constants: TPU v5e — 197 TFLOP/s bf16, 819 GB/s HBM,
    ~50 GB/s/link ICI (conservative single-link figure).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, Optional

import numpy as np

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = (
    "all-reduce",
    "all-gather",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

# matches e.g. "f32[16,128,1024]{2,1,0}" or "bf16[8]"
_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")


@dataclasses.dataclass(frozen=True)
class HardwareSpec:
    name: str = "tpu_v5e"
    peak_flops: float = 197e12        # bf16 per chip
    hbm_bw: float = 819e9             # bytes/s per chip
    link_bw: float = 50e9             # bytes/s per ICI link


HW_V5E = HardwareSpec()


def _shape_bytes(segment: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(segment):
        if dtype not in _DTYPE_BYTES:
            continue
        if dims == "":
            n = 1
        else:
            n = int(np.prod([int(d) for d in dims.split(",")]))
        total += n * _DTYPE_BYTES[dtype]
    return total


def collective_bytes_from_hlo(hlo_text: str) -> Dict[str, Dict[str, float]]:
    """Sum per-device payload bytes by collective type from HLO text.

    Counts each op's RESULT shapes (the bytes received per device). The
    ``*-start`` async forms are counted; their ``*-done`` twins are skipped
    (same payload, would double count).
    """
    out: Dict[str, Dict[str, float]] = {
        c: {"bytes": 0.0, "count": 0} for c in _COLLECTIVES
    }
    for line in hlo_text.splitlines():
        line = line.strip()
        if "=" not in line:
            continue
        for coll in _COLLECTIVES:
            # e.g. "%ar = f32[..] all-reduce(" / "all-reduce-start("
            m = re.search(rf"=\s+(.*?)\s+{coll}(-start)?\(", line)
            if m is None:
                continue
            if f"{coll}-done" in line:
                continue
            payload = _shape_bytes(m.group(1))
            out[coll]["bytes"] += payload
            out[coll]["count"] += 1
            break
    return out


def _maybe(obj, attr):
    try:
        v = getattr(obj, attr)
        return v() if callable(v) else v
    except Exception:
        return None


def memory_analysis_dict(compiled) -> Dict[str, Optional[float]]:
    ma = None
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return {}
    if ma is None:
        return {}
    keys = (
        "argument_size_in_bytes",
        "output_size_in_bytes",
        "temp_size_in_bytes",
        "alias_size_in_bytes",
        "generated_code_size_in_bytes",
    )
    return {k: _maybe(ma, k) for k in keys}


def roofline_from_compiled(
    compiled,
    num_devices: int,
    hw: HardwareSpec = HW_V5E,
    hlo_text: Optional[str] = None,
) -> Dict[str, Any]:
    """The three roofline terms + raw counters for one compiled step."""
    cost = {}
    try:
        cost = dict(compiled.cost_analysis() or {})
    except Exception:
        pass
    flops = float(cost.get("flops", 0.0))
    bytes_accessed = float(cost.get("bytes accessed", 0.0))

    text = hlo_text
    if text is None:
        try:
            text = compiled.as_text()
        except Exception:
            text = ""
    colls = collective_bytes_from_hlo(text or "")
    coll_bytes = sum(v["bytes"] for v in colls.values())

    t_comp = flops / hw.peak_flops
    t_mem = bytes_accessed / hw.hbm_bw
    t_coll = coll_bytes / hw.link_bw
    terms = {"compute_s": t_comp, "memory_s": t_mem, "collective_s": t_coll}
    dominant = max(terms, key=terms.get)
    return {
        "hw": hw.name,
        "num_devices": num_devices,
        "per_device_flops": flops,
        "per_device_bytes": bytes_accessed,
        "per_device_collective_bytes": coll_bytes,
        "collectives": colls,
        **terms,
        "dominant": dominant.replace("_s", ""),
        "memory_analysis": memory_analysis_dict(compiled),
    }


# ---------------------------------------------------------------------------
# inner-loop flop corrections
# ---------------------------------------------------------------------------
# XLA's HloCostAnalysis counts a while-loop body ONCE. The dry-run unrolls
# the LAYER scan (so per-layer ops and all collectives are exact), but
# within-layer chunk loops — blockwise exact attention, the Mamba chunk
# scan, the chunkwise mLSTM, the sequential sLSTM — remain loops. Their
# missing (trips - 1) * body_flops is added analytically here and reported
# as ``hlo_flops_corrected``. Formulas are documented per family; bytes are
# NOT corrected (the memory term carries a CPU-backend no-fusion bias that
# dwarfs this — see EXPERIMENTS.md §Roofline methodology).
_ATTN_BLOCK = 1024  # matches attention._BLOCK_Q/_BLOCK_K


def analytic_inner_loop_flops(cfg, seq_len: int, global_batch: int,
                              kind: str) -> float:
    """GLOBAL missing flops from loop bodies counted once (fwd+bwd)."""
    if kind == "decode":
        return 0.0  # single-token steps have no inner chunk loops
    t, b = seq_len, global_batch
    # train: fwd(1) + remat fwd(1) + bwd(2) instances of each loop; the HLO
    # contains each loop ~3x (fwd, recompute, bwd) each counted once, so the
    # missing multiplier is (trips-1) per instance ~= (trips-1)*4 flops-wise.
    factor = 4.0 if kind == "train" else 1.0
    missing = 0.0
    n_layers = cfg.num_layers
    pattern = list(cfg.block_pattern) * cfg.num_scanned_groups
    pattern = [cfg.block_pattern[0]] * cfg.first_k_dense + pattern

    for kind_b in pattern:
        mixer = kind_b.split("_")[0]
        if mixer in ("attn", "mla") and cfg.attention_mode == "exact" \
                and t > 2048:
            h = cfg.num_heads
            dh = (cfg.mla.qk_nope_head_dim + cfg.mla.qk_rope_head_dim
                  if mixer == "mla" else cfg.resolved_head_dim)
            dv = cfg.mla.v_head_dim if mixer == "mla" else cfg.resolved_head_dim
            bq = bk = min(_ATTN_BLOCK, t)
            nq, nk = -(-t // bq), -(-t // bk)
            trips = nq * nk
            body = 2.0 * b * h * bq * bk * (dh + dv)  # scores + pv matmuls
            missing += (trips - 1) * body * factor
        elif mixer == "mamba":
            mc = cfg.mamba
            d_in = mc.expand * cfg.d_model
            c = min(mc.scan_chunk, t)
            trips = -(-t // c)
            import math as _math

            logc = max(1.0, _math.log2(c))
            # assoc-scan (~4 flops/elem/level) + y-einsum + gates
            body = b * c * d_in * mc.d_state * (4.0 * logc + 8.0)
            missing += (trips - 1) * body * factor
        elif mixer == "mlstm":
            h = cfg.num_heads
            d_up = int(cfg.xlstm.proj_factor * cfg.d_model)
            dh = d_up // h
            c = min(cfg.xlstm.chunk, t)
            trips = -(-t // c)
            body = b * h * (4.0 * c * c * dh + 8.0 * c * dh * dh)
            missing += (trips - 1) * body * factor
        elif mixer == "slstm":
            h = cfg.num_heads
            dh = cfg.d_model // h
            body = b * h * (8.0 * dh * dh + 40.0 * dh)
            missing += (t - 1) * body * factor
    return missing


# ---------------------------------------------------------------------------
# MODEL_FLOPS (useful-work reference)
# ---------------------------------------------------------------------------
def count_params(shapes_tree, active_moe_fraction: Optional[float] = None):
    """(total, active) param counts from a ShapeDtypeStruct tree.

    ``active``: MoE expert weights scaled by top_k/num_experts (leaves under
    a "moe" path named w_gate/w_up/w_down).
    """
    import jax

    total = 0
    active = 0

    def _walk(path, node):
        nonlocal total, active
        if isinstance(node, dict):
            for k, v in node.items():
                _walk(path + (k,), v)
            return
        n = int(np.prod(node.shape))
        total += n
        frac = 1.0
        if active_moe_fraction is not None and "moe" in path and \
                path[-1] in ("w_gate", "w_up", "w_down"):
            frac = active_moe_fraction
        active += int(n * frac)

    _walk((), shapes_tree)
    return total, active


def model_flops(kind: str, n_active_params: int, tokens: int) -> float:
    """6*N*D for training, 2*N*D for inference forward passes."""
    if kind == "train":
        return 6.0 * n_active_params * tokens
    return 2.0 * n_active_params * tokens
