"""Subprocess script: data-parallel decode with the slot axis split over 8
host devices (2 lanes per device).

The continuous-batching Scheduler serves 16 requests over a (8, 1) host
mesh with 16 slots, so ``StepExecutor(mesh=)`` splits every cache leaf's
slot axis and each device decodes its own two lanes under shard_map. In
fp32 on the CPU, greedy generations must be identical to one device with
16 slots and to one device with 2 slots (the per-device batch).

Launched by tests/test_distributed_estimators.py with
XLA_FLAGS=--xla_force_host_platform_device_count=8.
"""
import dataclasses
import os

assert "--xla_force_host_platform_device_count=8" in \
    os.environ.get("XLA_FLAGS", ""), "launch via test_distributed_estimators"

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.models import init_model  # noqa: E402
from repro.serve import Request, Scheduler  # noqa: E402

assert len(jax.devices()) == 8
SLOTS = 16

cfg = get_config("qwen3-1.7b", smoke=True, attention_mode="rm")
cfg = dataclasses.replace(cfg, compute_dtype="float32")
params = init_model(cfg, jax.random.PRNGKey(0))
rng = np.random.default_rng(0)
prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
           for n in rng.integers(3, 40, size=SLOTS)]


def run(num_slots, mesh=None):
    eng = Scheduler(cfg, params, num_slots=num_slots, max_len=64, mesh=mesh)
    if mesh is not None:
        # every cache leaf is split over the slot axis: 2 lanes per device
        for leaf in jax.tree_util.tree_leaves(eng.executor.cache):
            shards = leaf.addressable_shards
            assert len({s.device for s in shards}) == 8, leaf.shape
            assert all(s.data.size * 8 == leaf.size for s in shards), \
                leaf.shape
    for i, p in enumerate(prompts):
        eng.submit(Request(request_id=i, prompt=p, max_new_tokens=6))
    done = eng.run(max_iters=1000)
    assert sorted(done) == list(range(SLOTS)), sorted(done)
    return {i: done[i].generated for i in done}


got_dp = run(SLOTS, make_host_mesh())
got_wide = run(SLOTS)
got_narrow = run(SLOTS // 8)
assert all(len(g) == 6 for g in got_dp.values())
assert got_dp == got_wide, (got_dp, got_wide)
assert got_dp == got_narrow, (got_dp, got_narrow)
print("split-lane DP decode matches single-device generations")
