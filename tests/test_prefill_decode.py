"""Prefill/decode consistency across ALL mixer families: prefill logits must
equal full-forward logits, and prefill->decode must equal forward over the
extended sequence (the invariant the serving engine relies on)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import (
    decode_step,
    forward,
    init_model,
    prefill,
)

# one representative per mixer family (plus rm mode)
CASES = [
    ("qwen3-1.7b", "exact"),
    ("qwen3-1.7b", "rm"),
    ("h2o-danube-3-4b", "exact"),       # sliding window
    ("deepseek-v2-lite-16b", "exact"),  # MLA + MoE + shared experts
    ("mixtral-8x7b", "exact"),          # MoE + SWA
    ("jamba-v0.1-52b", "exact"),        # mamba hybrid
    ("xlstm-350m", None),               # mlstm + slstm
]


@pytest.mark.parametrize("arch,mode", CASES,
                         ids=[f"{a}-{m}" for a, m in CASES])
def test_prefill_matches_forward_and_decode_continues(arch, mode):
    cfg = get_config(arch, smoke=True, attention_mode=mode)
    cfg = dataclasses.replace(cfg, compute_dtype="float32")
    if cfg.moe is not None:
        # capacity-based MoE drops depend on batch composition (prefill sees
        # 12 tokens, forward sees 15) — lift capacity so routing is dropless
        # and the paths are exactly comparable.
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    params = init_model(cfg, jax.random.PRNGKey(0))
    b, t_prompt, t_extra = 2, 12, 3
    key = jax.random.PRNGKey(1)
    tokens = jax.random.randint(key, (b, t_prompt + t_extra), 0,
                                cfg.vocab_size)

    # full forward over the whole sequence = ground truth
    full_logits, _ = forward(params, cfg, {"tokens": tokens})

    # prefill over the prompt
    pre_logits, cache = prefill(params, cfg,
                                {"tokens": tokens[:, :t_prompt]}, max_len=32)
    np.testing.assert_allclose(
        np.asarray(pre_logits), np.asarray(full_logits[:, :t_prompt]),
        rtol=2e-3, atol=2e-3,
    )

    # decode the extra tokens one by one; logits must match full forward
    for i in range(t_extra):
        pos = jnp.full((b,), t_prompt + i, jnp.int32)
        step_logits, cache = decode_step(params, cfg, cache,
                                         tokens[:, t_prompt + i][:, None],
                                         pos)
        np.testing.assert_allclose(
            np.asarray(step_logits[:, 0]),
            np.asarray(full_logits[:, t_prompt + i]),
            rtol=5e-3, atol=5e-3,
        )


def test_cell_enumeration_rules():
    from repro.configs import get_config, list_archs
    from repro.launch.shapes import SHAPES, enumerate_cells

    archs = list_archs()
    cfgs = {a: get_config(a) for a in archs}
    cells = enumerate_cells(archs, cfgs)
    assert len(cells) == len(archs) * len(SHAPES)  # 40 cells
    by_key = {(c.arch, c.shape): c for c in cells}
    # encoder-only skips
    assert by_key[("hubert-xlarge", "decode_32k")].skipped
    assert by_key[("hubert-xlarge", "long_500k")].skipped
    assert not by_key[("hubert-xlarge", "prefill_32k")].skipped
    # long_500k: rm for softmax archs, native for ssm/hybrid
    assert by_key[("qwen2-7b", "long_500k")].attention_mode == "rm"
    assert by_key[("mixtral-8x7b", "long_500k")].attention_mode == "rm"
    assert by_key[("xlstm-350m", "long_500k")].attention_mode == "exact"
    assert not by_key[("xlstm-350m", "long_500k")].skipped
    # all other shapes stay in the arch's configured mode
    assert by_key[("qwen2-7b", "train_4k")].attention_mode == "exact"


def test_input_specs_shapes():
    from repro.configs import get_config
    from repro.launch.shapes import input_specs

    cfg = get_config("qwen3-1.7b")
    s = input_specs(cfg, "train_4k")
    assert s["batch"]["tokens"].shape == (256, 4096)
    s = input_specs(cfg, "decode_32k")
    assert s["batch"]["tokens"].shape == (128, 1)
    assert "cache" in s
    # vlm: patch embeds carved out of seq_len
    cfg_v = get_config("internvl2-1b")
    s = input_specs(cfg_v, "train_4k")
    assert s["batch"]["embeds"].shape[1] == 256
    assert s["batch"]["tokens"].shape[1] == 4096 - 256
    # audio: embeds only
    cfg_a = get_config("hubert-xlarge")
    s = input_specs(cfg_a, "prefill_32k")
    assert s["batch"]["embeds"].shape == (32, 32768, 1280)


def test_blockwise_attention_mla_dv_ne_dh(monkeypatch):
    """Regression: blockwise attention with v_head_dim != qk head dim
    (MLA: 192 vs 128) — caught by the deepseek train_4k dry-run."""
    import repro.models.attention as A

    monkeypatch.setattr(A, "_BLOCKWISE_THRESHOLD", 16)
    monkeypatch.setattr(A, "_BLOCK_Q", 16)
    monkeypatch.setattr(A, "_BLOCK_K", 16)
    cfg = get_config("deepseek-v2-lite-16b", smoke=True)
    cfg = dataclasses.replace(cfg, compute_dtype="float32")
    params = init_model(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 48), 0,
                                cfg.vocab_size)
    logits, _ = forward(params, cfg, {"tokens": tokens})
    assert logits.shape == (2, 48, cfg.vocab_size)
    assert not bool(jnp.isnan(logits).any())
    # must match the small-path einsum attention
    monkeypatch.setattr(A, "_BLOCKWISE_THRESHOLD", 2048)
    logits2, _ = forward(params, cfg, {"tokens": tokens})
    np.testing.assert_allclose(np.asarray(logits), np.asarray(logits2),
                               rtol=2e-3, atol=2e-3)


def test_moe_shardmap_batch1(monkeypatch):
    """Regression: MoE shard_map with batch=1 (long_500k) falls back to
    replicated tokens instead of failing to shard."""
    from repro.distributed.sharding import logical_rules_context
    from repro.launch.mesh import make_mesh

    cfg = get_config("mixtral-8x7b", smoke=True)
    cfg = dataclasses.replace(cfg, compute_dtype="float32")
    params = init_model(cfg, jax.random.PRNGKey(0))
    tokens = jnp.ones((1, 4), jnp.int32)
    mesh = make_mesh((1, 1), ("data", "model"))
    with logical_rules_context(mesh):
        logits, _ = jax.jit(
            lambda p, b: forward(p, cfg, b))(params, {"tokens": tokens})
    assert not bool(jnp.isnan(logits).any())
