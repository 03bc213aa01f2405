"""Faults a served cell can have, planted in the program under the harness.

Each takes an object with ``setattr(obj, name, value)`` (pytest's
``monkeypatch``, or ``builtins`` where the process ends after one run) and
breaks the timed path underneath a run; the check has to read each run as
not correct.
"""


def state_unchanged(mp):
    """The decode step returns the cache it was given."""
    from repro.serve import executor as ex

    def decode(self, tokens, positions):
        logits, _ = ex._decode_compiled(self.params, self.cfg, self.cache,
                                        tokens, positions)
        return logits

    mp.setattr(ex.StepExecutor, "decode", decode)


def half_lanes(mp):
    """Half of the lanes left out: they get the other half's logits."""
    from repro.serve import executor as ex

    real = ex.StepExecutor.decode

    def decode(self, tokens, positions):
        logits = real(self, tokens, positions)
        half = logits.shape[0] // 2
        return logits.at[half:].set(logits[:logits.shape[0] - half])

    mp.setattr(ex.StepExecutor, "decode", decode)


def token_altered(mp):
    """Each sampled token is replaced by its neighbour in the vocabulary."""
    from repro.serve import scheduler

    real = scheduler.sample_token

    def sample(logits, key, temperature=0.0, top_k=0):
        return (real(logits, key, temperature, top_k) + 1) % logits.shape[-1]

    mp.setattr(scheduler, "sample_token", sample)


FAULTS = {f.__name__: f for f in (state_unchanged, half_lanes, token_altered)}
