"""The check that decides ``correct``, driven through a whole run at the
tests' tiny size on the CPU: the harness's look for a chip is skipped, the
rest of a run (weights, warm-up, window, reference) is the benchmark's own.

A sound run is correct. The control (the reference in fp8 in the program's
place) and each fault a served cell can have, planted in the program under
the harness (``faults.py``), make it not correct. The check compares one
request of every lane and at least 300 served tokens; the tiny
configurations' limit was set from 8 seeds per tiny cell on the CPU: the
program's widest gap read at most 0.22, the control's at least 1.74; the
limit is 0.6.
"""
import json
import sys
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH / "tests"))

from faults import FAULTS  # noqa: E402

DATA = BENCH / "tests" / "data"
CELL = "qwen3-exact.decode-batch"
SECONDS = 3.0
CACHE_KEYS = ("jax_compilation_cache_dir",
              "jax_persistent_cache_min_compile_time_secs",
              "jax_persistent_cache_min_entry_size_bytes")


@pytest.fixture(autouse=True)
def keep_jax_config(monkeypatch):
    """A run places JAX's compilation cache; give other tests theirs back."""
    import jax

    saved = {k: getattr(jax.config, k) for k in CACHE_KEYS}
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       str(BENCH.parent / ".xla-cache"))
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def _cell(mode, kind):
    """A benchmark cell's metrics, with the tiny configuration and traffic
    in place of the cell's own."""
    from harness import loader

    real = loader.cell(CELL)
    return dict(real,
                config=json.loads((DATA / f"tiny-{mode}.json").read_text()),
                traffic=json.loads((DATA / f"tiny-{kind}.json").read_text()))


def _run(mode, kind, seed, control=False):
    from harness import bench

    return bench.run_cell(_cell(mode, kind), seed, SECONDS, False,
                          t_start=time.perf_counter(), require_chip=False,
                          control=control)


@pytest.mark.parametrize("mode,kind", [("rm", "batch"), ("exact", "open")])
def test_sound_run_is_correct(mode, kind):
    out = _run(mode, kind, 2 ** 31 + 77)
    assert out["correct"], out["compared"]
    assert out["compared"]["served_tokens"]["value"] > 0
    assert out["attempted"] > 0 and out["failed"] == 0
    names = {m["name"] for m in _cell(mode, kind)["end_to_end"]}
    assert set(out["metrics"]) == names
    assert list(out)[-1] == "compared"


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_control_is_not_correct(seed):
    """The control in the program's place comes out not correct."""
    out = _run("rm", "batch", seed, control=True)
    assert not out["correct"], out["compared"]
    ctrl = out["compared"]["control_logit_gap"]
    assert ctrl["value"] > ctrl["limit"], out["compared"]


def test_second_family_needs_no_harness_edit():
    """An OLMo configuration (layer norms with no parameters, no qk-norm,
    multi-head attention) runs through the same harness and reference."""
    from harness import loader

    cell = dict(loader.cell(CELL),
                config=json.loads((DATA / "tiny-olmo-rm.json").read_text()),
                traffic=json.loads((DATA / "tiny-batch.json").read_text()))
    from harness import bench

    out = bench.run_cell(cell, 2 ** 31 + 5, SECONDS, False,
                         t_start=time.perf_counter(), require_chip=False)
    assert out["correct"], out["compared"]
    assert out["compared"]["served_tokens"]["value"] > 0


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_not_correct(fault, monkeypatch):
    sys.path.insert(0, str(BENCH.parent / "src"))
    FAULTS[fault](monkeypatch)
    out = _run("rm", "batch", 4242)
    assert not out["correct"], out["compared"]
