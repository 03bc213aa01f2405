"""Token sampling: greedy / temperature / top-k, one device program a call.

``sample_token`` takes either one PRNG key for the whole batch (the legacy
``ServingEngine``'s engine-global stream) or a leading batch of keys, one
per row (the ``Scheduler``'s per-request streams). With a batch of keys,
row ``i`` draws ``jax.random.categorical(key[i], logits[i][None] / t[i])``
at shape ``[1, V]``: the same bits a one-row call with that key draws, so
batching the lanes changes no token.

``temperature`` is a float shared by every row, or, with a batch of keys,
a ``[B]`` array when rows differ; rows at ``<= 0`` take the argmax. A
float ``<= 0`` compiles only the argmax.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _batched_keys(key: jax.Array) -> bool:
    """A batch of keys has a leading axis past one key's shape."""
    one_key_ndim = 0 if jax.dtypes.issubdtype(key.dtype,
                                              jax.dtypes.prng_key) else 1
    return key.ndim > one_key_ndim


def _draw(logits, key, temperature, top_k):
    """``[B, V]`` logits at a scalar temperature, one key -> ``[B]``."""
    t = temperature.astype(logits.dtype)
    scaled = logits / jnp.where(t > 0, t, 1.0)
    if top_k > 0:
        kth = jnp.sort(scaled, axis=-1)[:, -top_k][:, None]
        scaled = jnp.where(scaled < kth, -1e30, scaled)
    return jax.random.categorical(key, scaled, axis=-1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("greedy", "top_k"))
def _sample(logits, key, temperature, *, greedy, top_k):
    argmax = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if greedy:
        return argmax
    if not _batched_keys(key):
        return _draw(logits, key, temperature, top_k)
    temps = jnp.broadcast_to(temperature, logits.shape[:1])
    drawn = jax.vmap(lambda row, k, t: _draw(row[None], k, t, top_k)[0])(
        logits, key, temps)
    return jnp.where(temps > 0, drawn, argmax)


def sample_token(
    logits: jax.Array,          # [B, V] (fp32)
    key: jax.Array,             # one key, or [B] keys
    temperature=0.0,            # float, or [B] per row with [B] keys
    top_k: int = 0,
) -> jax.Array:                 # [B] int32
    greedy = np.ndim(temperature) == 0 and float(temperature) <= 0.0
    return _sample(logits, key, np.asarray(temperature, np.float32),
                   greedy=greedy, top_k=top_k)
