"""The batched sampler: one ``sample_token`` call over every decode lane,
with a batch of per-request keys, draws exactly the tokens the per-lane
loop drew (a host ``fold_in`` key, a ``[1, V]`` slice and a single-key
call); and a scheduler step reads its tokens back in one host sync.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import init_model
from repro.serve import Request, Scheduler, sample_token
from repro.serve.scheduler import _lane_keys

SLOTS, VOCAB = 8, 1024
IDLE = None

# per-lane temperature; IDLE marks a lane with no request
CASES = {
    "greedy": [0.0] * SLOTS,
    "sampled": [0.7] * SLOTS,
    "mixed_with_idle": [0.0, 1.3, IDLE, 0.7, 0.0, IDLE, 2.0, 0.7],
}


def _per_lane_loop(logits, base, rids, idxs, temps, top_k):
    """The reference: each busy lane alone, on a key made on the host."""
    out = {}
    for i, t in enumerate(temps):
        if t is IDLE:
            continue
        key = jax.random.fold_in(jax.random.fold_in(base, rids[i]), idxs[i])
        row = logits[i:i + 1]
        tok = int(sample_token(row, key, t, top_k)[0])
        # and the sampler's plain math, eager, one lane at a time
        if t <= 0:
            plain = jnp.argmax(row, axis=-1)
        else:
            scaled = row / t
            if top_k > 0:
                kth = jnp.sort(scaled, axis=-1)[:, -top_k][:, None]
                scaled = jnp.where(scaled < kth, -1e30, scaled)
            plain = jax.random.categorical(key, scaled, axis=-1)
        assert tok == int(plain[0])
        out[i] = tok
    return out


@pytest.mark.parametrize("top_k", [0, 5])
@pytest.mark.parametrize("case", sorted(CASES))
def test_batched_sampling_matches_per_lane_loop(case, top_k):
    temps = CASES[case]
    rng = np.random.default_rng(7)
    logits = jnp.asarray(rng.normal(size=(SLOTS, VOCAB)) * 3, jnp.float32)
    base = jax.random.PRNGKey(11)
    rids = rng.integers(0, 1 << 30, size=SLOTS)
    idxs = rng.integers(0, 2000, size=SLOTS)
    ids = np.stack([rids, idxs]).astype(np.uint32)
    busy = [t for t in temps if t is not IDLE]
    temperature = (busy[0] if len(set(busy)) == 1
                   else np.asarray([0.0 if t is IDLE else t for t in temps],
                                   np.float32))

    keys = _lane_keys(base, ids)
    assert keys.shape == (SLOTS, 2)
    got = np.asarray(sample_token(logits, keys, temperature, top_k))
    assert got.shape == (SLOTS,) and got.dtype == np.int32
    want = _per_lane_loop(logits, base, rids, idxs, temps, top_k)
    assert {i: int(got[i]) for i in want} == want
    if case == "sampled":          # the draw is not the argmax everywhere
        assert any(want[i] != int(jnp.argmax(logits[i])) for i in want)


def test_lane_keys_match_host_fold_in():
    base = jax.random.PRNGKey(3)
    ids = np.array([[0, 5, 1 << 30, 7], [0, 0, 3, 1999]], np.uint32)
    keys = np.asarray(_lane_keys(base, ids))
    for i in range(ids.shape[1]):
        host = jax.random.fold_in(jax.random.fold_in(base, int(ids[0, i])),
                                  int(ids[1, i]))
        np.testing.assert_array_equal(keys[i], np.asarray(host))


def test_single_key_keeps_one_draw_over_the_batch():
    """The legacy engine's call: one key for the whole ``[B, V]`` batch."""
    logits = jnp.asarray(np.random.default_rng(2).normal(size=(4, VOCAB)),
                         jnp.float32)
    key = jax.random.PRNGKey(5)
    got = sample_token(logits, key, 0.9)
    want = jax.random.categorical(key, logits / 0.9, axis=-1)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("qwen3-1.7b", smoke=True)
    cfg = dataclasses.replace(cfg, compute_dtype="float32")
    return cfg, init_model(cfg, jax.random.PRNGKey(0))


def test_decode_step_reads_its_tokens_in_one_host_sync(setup, monkeypatch):
    """A step with every lane busy and nothing to admit makes exactly one
    device-to-host read, whatever the number of lanes."""
    cfg, params = setup
    sched = Scheduler(cfg, params, num_slots=3, max_len=32, rng_seed=0)
    rng = np.random.default_rng(0)
    for i, t in enumerate((0.0, 0.8, 0.0)):
        sched.submit(Request(request_id=i,
                             prompt=rng.integers(0, 512, size=4 + i),
                             max_new_tokens=6, temperature=t))
    sched.step()                         # admits all three, decodes once
    assert all(s is not None for s in sched.slots)

    # a jax.Array reaches the host through the buffer protocol
    # (np.asarray on the CPU) or ``_value`` (int(), tolist(), __array__)
    reads = []
    impl = type(jnp.zeros(1))
    buffer, value = impl.__buffer__, impl._value

    def read_buffer(self, flags):
        reads.append(self.shape)
        return buffer(self, flags)

    def read_value(self):
        reads.append(self.shape)
        return value.fget(self)

    monkeypatch.setattr(impl, "__buffer__", read_buffer)
    monkeypatch.setattr(impl, "_value", property(read_value))
    info = sched.step()
    assert info.active == 3 and not info.admitted
    assert reads == [(3,)]


def test_every_step_goes_through_the_module_sample_token(setup,
                                                          monkeypatch):
    """The scheduler calls ``repro.serve.scheduler.sample_token`` at call
    time, once per admission and once per decode step over all lanes, so
    a wrapper there sees (and can alter) every token served."""
    from repro.serve import scheduler

    cfg, params = setup
    real, calls = scheduler.sample_token, []

    def shifted(logits, key, temperature=0.0, top_k=0):
        calls.append(logits.shape)
        return (real(logits, key, temperature, top_k) + 1) % logits.shape[-1]

    def run():
        sched = Scheduler(cfg, params, num_slots=2, max_len=32, rng_seed=0)
        rng = np.random.default_rng(3)
        for i, n_new in enumerate((3, 2, 4)):
            sched.submit(Request(request_id=i,
                                 prompt=rng.integers(0, 512, size=5),
                                 max_new_tokens=n_new))
        infos = []
        while sched.pending():
            infos.append(sched.step())
        return sched.finished, infos

    plain, _ = run()
    monkeypatch.setattr(scheduler, "sample_token", shifted)
    altered, infos = run()
    steps = sum(1 for i in infos if i.active)
    admits = sum(len(i.admitted) for i in infos)
    assert len(calls) == steps + admits
    assert sorted(calls) == sorted([(1, cfg.vocab_size)] * admits
                                   + [(2, cfg.vocab_size)] * steps)
    for rid, state in altered.items():
        # the first token is the clean one plus one; later tokens follow
        # a different prefix, so only the first is compared
        assert state.generated[0] == (plain[rid].generated[0] + 1) % \
            cfg.vocab_size
