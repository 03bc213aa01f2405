"""Checkpoint manager + fault tolerance tests (atomicity, keep-k, restarts,
elastic resharding)."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.train.checkpoint import CheckpointManager
from repro.train.fault import StragglerMonitor, elastic_remesh, run_with_restarts


def _state(v=0.0):
    return {
        "params": {"w": jnp.full((4, 8), v), "b": jnp.zeros((8,))},
        "step": jnp.asarray(int(v), jnp.int32),
    }


def test_checkpoint_roundtrip(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=2)
    mgr.save(10, _state(1.0))
    out = mgr.restore()
    np.testing.assert_array_equal(np.asarray(out["params"]["w"]), 1.0)
    assert int(out["step"]) == 1


def test_checkpoint_keep_k_gc(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=2)
    for s in (1, 2, 3, 4):
        mgr.save(s, _state(float(s)))
    assert mgr.available_steps() == [3, 4]


def test_checkpoint_structure_mismatch_detected(tmp_path):
    mgr = CheckpointManager(tmp_path)
    mgr.save(1, _state())
    bad_template = {"params": {"w": jnp.zeros((4, 8))}, "extra": jnp.zeros(())}
    with pytest.raises(ValueError, match="structure mismatch"):
        mgr.restore(template=bad_template)


def test_checkpoint_atomic_publish(tmp_path):
    """A leftover tmp dir never shadows a valid checkpoint."""
    mgr = CheckpointManager(tmp_path)
    mgr.save(5, _state(5.0))
    # simulate a crashed partial write
    (tmp_path / "tmp.6.999").mkdir()
    assert mgr.latest_step() == 5
    out = mgr.restore()
    assert int(out["step"]) == 5


def test_run_with_restarts_recovers(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=3)
    crashed = {"count": 0}

    def step_fn(state, step):
        if step == 7 and crashed["count"] == 0:
            crashed["count"] += 1
            raise RuntimeError("simulated node failure")
        return {**state, "step": jnp.asarray(step + 1, jnp.int32),
                "params": state["params"]}

    final = run_with_restarts(step_fn, _state(), num_steps=12,
                              ckpt_manager=mgr, checkpoint_every=5,
                              max_restarts=2)
    assert crashed["count"] == 1
    assert int(final["step"]) == 12


def test_run_with_restarts_gives_up(tmp_path):
    mgr = CheckpointManager(tmp_path)

    def always_fail(state, step):
        raise RuntimeError("persistent failure")

    with pytest.raises(RuntimeError, match="persistent"):
        run_with_restarts(always_fail, _state(), 5, mgr, max_restarts=2)


def test_straggler_monitor():
    mon = StragglerMonitor(threshold=2.0, warmup_steps=2)
    flags = [mon.record(i, 0.1) for i in range(8)]
    assert not any(flags)
    assert mon.record(8, 0.5)          # 5x the mean -> flagged
    assert len(mon.events) == 1
    assert mon.events[0]["step"] == 8


def test_elastic_remesh_single_device(tmp_path):
    """Checkpoint written under one topology restores onto another."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.mesh import make_mesh as make_auto_mesh

    mgr = CheckpointManager(tmp_path)
    mgr.save(3, _state(3.0))

    def make_mesh():
        return make_auto_mesh((1, 1), ("data", "model"))

    def make_shardings(mesh):
        return jax.tree_util.tree_map(
            lambda _: NamedSharding(mesh, P()), _state()
        )

    mesh, state = elastic_remesh(mgr, make_mesh, make_shardings)
    np.testing.assert_array_equal(np.asarray(state["params"]["w"]), 3.0)
