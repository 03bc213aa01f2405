"""Subprocess script: training on a (2, 4) DP x TP mesh must match
single-device training numerically (the core SPMD-correctness invariant).

Launched by tests/test_distributed.py with
XLA_FLAGS=--xla_force_host_platform_device_count=8.
"""
import os

assert "--xla_force_host_platform_device_count=8" in \
    os.environ.get("XLA_FLAGS", ""), "launch via test_distributed.py"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.data.synthetic import SyntheticLMDataset  # noqa: E402
from repro.distributed.sharding import (  # noqa: E402
    batch_partition_specs,
    logical_rules_context,
    params_partition_specs,
)
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.train.steps import (  # noqa: E402
    TrainHyper,
    init_train_state,
    make_train_step,
)

assert len(jax.devices()) == 8

cfg = get_config("qwen3-1.7b", smoke=True)
# fp32 end-to-end so single-device and sharded runs are bit-comparable
import dataclasses  # noqa: E402

cfg = dataclasses.replace(cfg, compute_dtype="float32", remat=False)
hyper = TrainHyper(peak_lr=1e-3, warmup_steps=2, total_steps=10)
data = SyntheticLMDataset(vocab_size=cfg.vocab_size, seq_len=64,
                          global_batch=8)
step_fn = make_train_step(cfg, hyper)

# ---- single device ---------------------------------------------------------
state1 = init_train_state(cfg, jax.random.PRNGKey(0), hyper)
step1 = jax.jit(step_fn)
losses1 = []
for i in range(4):
    state1, m = step1(state1, data.batch_at(i))
    losses1.append(float(m["loss"]))

# ---- 2x4 mesh ---------------------------------------------------------------
mesh = make_mesh((2, 4), ("data", "model"))
with logical_rules_context(mesh) as rules:
    state2 = init_train_state(cfg, jax.random.PRNGKey(0), hyper)
    pspec = params_partition_specs(state2["params"], mesh, rules)
    sspec = {"params": pspec, "opt": {"mu": pspec, "nu": pspec, "step": P()},
             "step": P()}
    sshard = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), sspec,
        is_leaf=lambda s: isinstance(s, P))
    state2 = jax.device_put(state2, sshard)
    bspec = batch_partition_specs(data.batch_at(0), mesh, rules)
    bshard = jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), bspec,
                                    is_leaf=lambda s: isinstance(s, P))
    step2 = jax.jit(step_fn, in_shardings=(sshard, bshard),
                    out_shardings=(sshard, None))
    losses2 = []
    for i in range(4):
        batch = jax.device_put(data.batch_at(i), bshard)
        state2, m = step2(state2, batch)
        losses2.append(float(m["loss"]))

print("single:", losses1)
print("mesh  :", losses2)
# fp32 end-to-end, but XLA's sharded all-reduce ordering differs from the
# single-device reduction; observed divergence on CPU pins is ~6e-4 after
# 4 steps, so the bound is 1e-3 (still catches real SPMD bugs, which show
# up at 1e-1+ or as NaNs).
np.testing.assert_allclose(losses1, losses2, rtol=1e-3, atol=1e-3)
assert losses1[-1] < losses1[0], "loss should decrease"
print("DP/TP EQUIVALENCE OK")
