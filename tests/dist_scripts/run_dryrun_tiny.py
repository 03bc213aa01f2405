"""Subprocess script: the dry-run machinery end-to-end on an 8-device mesh
with smoke configs — proves lower+compile+roofline extraction works on a
REAL multi-device mesh (the 512-device run uses the same code path).
"""
import os

assert "--xla_force_host_platform_device_count=8" in \
    os.environ.get("XLA_FLAGS", "")

import dataclasses  # noqa: E402

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.analysis.roofline import roofline_from_compiled  # noqa: E402
from repro.configs import get_config  # noqa: E402
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
mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))

for arch in ("qwen3-1.7b", "mixtral-8x7b", "jamba-v0.1-52b", "xlstm-350m"):
    cfg = get_config(arch, smoke=True)
    hyper = TrainHyper()
    with logical_rules_context(mesh) as rules:
        state_sds = jax.eval_shape(
            lambda: init_train_state(cfg, jax.random.PRNGKey(0), hyper))
        pspec = params_partition_specs(state_sds["params"], mesh, rules)
        sspec = {"params": pspec,
                 "opt": {"mu": pspec, "nu": pspec, "step": P()}, "step": P()}
        sshard = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), sspec,
            is_leaf=lambda s: isinstance(s, P))
        batch_sds = {
            "tokens": jax.ShapeDtypeStruct((8, 32), np.int32),
            "targets": jax.ShapeDtypeStruct((8, 32), np.int32),
        }
        bspec = batch_partition_specs(batch_sds, mesh, rules)
        bshard = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), bspec,
            is_leaf=lambda s: isinstance(s, P))
        step = make_train_step(cfg, hyper)
        lowered = jax.jit(step, in_shardings=(sshard, bshard),
                          out_shardings=(sshard, None)).lower(
            state_sds, batch_sds)
        compiled = lowered.compile()
        roof = roofline_from_compiled(compiled, mesh.size)
        assert roof["per_device_flops"] > 0
        mem = roof["memory_analysis"]
        assert mem.get("temp_size_in_bytes") is not None
        print(f"{arch}: flops/dev={roof['per_device_flops']:.3g} "
              f"coll/dev={roof['per_device_collective_bytes']:.3g} OK")
print("TINY DRYRUN OK")
