"""Continuous-batching scheduler: per-step slot admission/eviction over the
shared :class:`~repro.serve.executor.StepExecutor`.

This is the serving frontend ROADMAP item 2 asks for — the O(1)-state
decode lanes the paper's feature maps buy us, driven by a scheduler whose
correctness contract is property-tested (tests/test_scheduler_invariants.py)
rather than assumed:

  * **per-step admission** — every :meth:`step` first admits queued
    requests into freed slots (prefill) while other slots keep decoding;
    no batch-synchronous barriers. Compiled shapes stay bounded: one
    decode shape per (num_slots, max_len) and one prefill shape per
    effective bucket.
  * **FIFO + priority queues with backpressure** — requests carry a
    ``priority`` (higher admits first; FIFO within a priority class via a
    monotone submission sequence number). A full engine NEVER drops work:
    requests wait in the queue until a slot frees (``cache_full`` is a
    per-request finish reason, not an admission failure).
  * **per-request deterministic sampling** — request ``r``'s ``t``-th
    token is sampled with ``fold_in(fold_in(key(seed), r), t)``, so every
    request's output is a pure function of ``(rng_seed, request)`` —
    independent of slot count, admission order, co-batched requests,
    evictions and restarts. This is the bit-identical-to-sequential-oracle
    invariant the test suite pins, and what makes the recovery contract
    below possible. (The legacy ``ServingEngine`` splits one engine-global
    key instead, so its temperature>0 streams depend on scheduling.)
  * **eviction + restart-from-scratch recovery** — :meth:`evict` preempts
    a slot and re-queues its request at its ORIGINAL queue position
    (sequence number preserved → no starvation); the request replays from
    its prompt and, by the key discipline above, regenerates the exact
    same tokens. A failed prefill/decode step (when ``max_restarts > 0``)
    triggers the same path for every in-flight slot plus a fresh decode
    cache — at-least-once token delivery with bit-identical replay
    (docs/serving.md).

Observability: the full request lifecycle (``request/submit`` →
``request/admit``/``admit`` span → ``prefill`` span → ``decode/step``
spans → ``request/finish``, plus ``request/evict``/``evict`` spans and
``serve/restart`` events), the ``serve/queue_age_s`` gauge (age of the
oldest queued request) and the TTFT / inter-token / tokens-per-sec
histograms, all on the injectable ``repro.obs`` clock — the whole
scheduler runs deterministically under ``FakeClock``. The host phases of
a tick are spans too: ``step`` holds the whole tick; each decode step has
one ``sample`` span (the lanes' keys, made on the device, and one
``sample_token`` over all ``num_slots`` lanes; attribute ``lanes``, the
busy ones) and one ``fetch`` span (the one device-to-host read of the
step's ``[num_slots]`` tokens), and each admission its own pair for the
first token. ``serve/host_syncs`` counts the fetches and
``serve/sampled_lanes`` the busy lanes those calls served. Every span is
also a ``repro.<name>`` span in a JAX profile, on the clock of the device
operations.
"""
from __future__ import annotations

import dataclasses
import heapq
import warnings
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import resolve as _obs_resolve
from repro.serve.engine import Request, RequestState
from repro.serve.executor import StepExecutor
from repro.serve.sampler import sample_token

__all__ = ["Scheduler", "StepInfo"]


@jax.jit
def _lane_keys(base: jax.Array, ids: jax.Array) -> jax.Array:
    """``[2, n]`` uint32 (request id, token index) -> ``[n]`` keys, each
    ``fold_in(fold_in(base, rid), tok_idx)``: one program for a step."""
    return jax.vmap(lambda rid, idx: jax.random.fold_in(
        jax.random.fold_in(base, rid), idx))(ids[0], ids[1])


@dataclasses.dataclass
class StepInfo:
    """What one scheduler tick did — the loadgen's accounting unit."""

    admitted: List[int] = dataclasses.field(default_factory=list)
    finished: List[int] = dataclasses.field(default_factory=list)
    evicted: List[int] = dataclasses.field(default_factory=list)
    active: int = 0                 # slots that ran the decode this tick
    new_tokens: int = 0             # tokens emitted (prefill + decode)
    restarted: bool = False         # a fault-recovery respawn happened
    t_start: float = 0.0
    t_end: float = 0.0


class Scheduler:
    """Continuous-batching serving scheduler (see module docstring).

    Args:
        cfg: frozen model config (validated by the executor).
        params: model params pytree.
        num_slots: decode lanes.
        max_len: per-lane cache length (scratch position is the last).
        rng_seed: base PRNG seed; request ``r``'s stream is
            ``fold_in(PRNGKey(rng_seed), r)``.
        buckets: prefill bucket ladder override (validated
            sorted/positive, clipped to ``max_len``).
        max_admits_per_step: cap on admissions (prefills) per tick —
            bounds per-step latency contributed by prefill work; ``None``
            admits into every free slot.
        max_restarts: fault-recovery budget. 0 (default) disables
            recovery: executor exceptions propagate. With N > 0, up to N
            failed steps re-queue all in-flight requests onto a fresh
            decode cache and continue; the N+1-th failure re-raises.
        straggler_monitor: optional ``repro.train.fault.StragglerMonitor``
            — decode-step wall times are ``record``-ed on it, reusing the
            training stack's straggler detection for serving.
        mesh: optional DP mesh (slot axis sharded; DESIGN.md §10).
        obs: optional ``repro.obs.Obs``; ``None`` is a strict no-op.
    """

    def __init__(self, cfg: Any, params: Any, *, num_slots: int = 4,
                 max_len: int = 1024, rng_seed: int = 0,
                 buckets: Optional[Sequence[int]] = None,
                 max_admits_per_step: Optional[int] = None,
                 max_restarts: int = 0, straggler_monitor: Any = None,
                 mesh: Any = None, obs: Any = None,
                 accuracy_tiers: Optional[Dict[str, int]] = None):
        self.obs = _obs_resolve(obs)
        # Per-request accuracy tiers (docs/adaptive.md): tier name ->
        # feature generation count. The executor splits the RM budget into
        # max(tiers) equal fold_in-keyed generations; a request at tier g
        # is certified against the g-generation feature prefix's (eps,
        # delta) bound. Validation (rm mode, even split, range) lives in
        # the executor so a bad tier map fails at construction.
        self.accuracy_tiers: Optional[Dict[str, int]] = None
        feature_generations = 1
        if accuracy_tiers:
            for name, gens in accuracy_tiers.items():
                if int(gens) < 1:
                    raise ValueError(
                        f"accuracy tier {name!r} must map to >= 1 "
                        f"generations, got {gens}")
            self.accuracy_tiers = {k: int(v)
                                   for k, v in accuracy_tiers.items()}
            feature_generations = max(self.accuracy_tiers.values())
        self.executor = StepExecutor(cfg, params, num_slots, max_len,
                                     buckets=buckets, mesh=mesh,
                                     feature_generations=feature_generations)
        self.estimator = self.executor.estimator
        self.fused_attention = self.executor.fused_attention
        self.cfg = cfg
        self.num_slots = int(num_slots)
        self.max_len = int(max_len)
        self.mesh = mesh
        self.max_admits_per_step = max_admits_per_step
        self.max_restarts = int(max_restarts)
        self.straggler_monitor = straggler_monitor
        self.restarts = 0
        self.slots: List[Optional[RequestState]] = [None] * self.num_slots
        self.finished: Dict[int, RequestState] = {}
        self._heap: List[Tuple[int, int, Request]] = []  # (-prio, seq, req)
        self._seq = 0
        self._seq_of: Dict[int, int] = {}
        self._t_submit: Dict[int, float] = {}
        self._attempts: Dict[int, int] = {}
        self._base_key = jax.random.PRNGKey(rng_seed)
        self._tokens = np.zeros((self.num_slots, 1), np.int32)
        self._positions = np.full((self.num_slots,),
                                  self.executor.scratch_position, np.int32)
        self._step_idx = 0

    # -- public API -----------------------------------------------------------
    @property
    def params(self):
        return self.executor.params

    @property
    def cache(self):
        return self.executor.cache

    @property
    def queue_depth(self) -> int:
        return len(self._heap)

    def pending(self) -> bool:
        """Any work left — queued or mid-decode?"""
        return bool(self._heap) or any(s is not None for s in self.slots)

    def submit(self, request: Request) -> None:
        """Enqueue a request (backpressure: never drops, never blocks).

        Request ids must be unique across the scheduler's lifetime — the
        per-request PRNG stream and the finished map are keyed on them.
        """
        rid = request.request_id
        if rid in self._seq_of or rid in self.finished or any(
                s is not None and s.request.request_id == rid
                for s in self.slots):
            raise ValueError(f"duplicate request_id {rid}: ids key the "
                             "per-request PRNG stream and result map")
        if len(request.prompt) >= self.max_len:
            raise ValueError(
                f"prompt length {len(request.prompt)} exceeds engine "
                f"max_len {self.max_len}: the decode cache has no room "
                "for generated tokens; raise max_len or truncate")
        if request.accuracy_tier is not None:
            if not self.accuracy_tiers:
                raise ValueError(
                    f"request {rid} asks for accuracy_tier="
                    f"{request.accuracy_tier!r} but the scheduler was "
                    "built without accuracy_tiers=")
            if request.accuracy_tier not in self.accuracy_tiers:
                raise ValueError(
                    f"unknown accuracy_tier {request.accuracy_tier!r} "
                    f"for request {rid}; configured tiers: "
                    f"{sorted(self.accuracy_tiers)}")
        seq = self._seq
        self._seq += 1
        self._seq_of[rid] = seq
        self._t_submit[rid] = self.obs.now()
        heapq.heappush(self._heap, (-int(request.priority), seq, request))
        self.obs.event("request/submit", request_id=rid,
                       prompt_len=len(request.prompt),
                       priority=int(request.priority),
                       accuracy_tier=request.accuracy_tier)
        self.obs.counter("serve/requests_submitted")
        self.obs.gauge("serve/queue_depth", len(self._heap))

    def step(self) -> StepInfo:
        """One scheduler tick: admit into free slots, then decode the batch.

        Returns a :class:`StepInfo` describing what happened. With
        ``max_restarts > 0``, an executor failure inside the tick re-queues
        every in-flight request onto a fresh decode cache (restart-from-
        scratch recovery) instead of propagating, up to the budget.
        """
        with self.obs.span("step"):
            self._step_idx += 1
            info = StepInfo(t_start=self.obs.now())
            try:
                self._admit_phase(info)
                self._decode_phase(info)
            except Exception as e:  # noqa: BLE001 - bounded restart semantics
                if self.restarts >= self.max_restarts:
                    raise
                self.restarts += 1
                self._recover(info, repr(e))
            info.t_end = self.obs.now()
        return info

    def evict(self, slot: int, reason: str = "preempted") -> Request:
        """Preempt ``slot``: discard its decode state, re-queue its request.

        The request keeps its ORIGINAL submission sequence number, so it
        re-enters the queue at its old position (no starvation) and — by
        the per-request key discipline — will regenerate the exact same
        tokens from scratch on re-admission (the recovery contract,
        docs/serving.md).
        """
        state = self.slots[slot]
        if state is None:
            raise ValueError(f"slot {slot} is not occupied")
        req = state.request
        with self.obs.span("evict", request_id=req.request_id, slot=slot,
                           reason=reason):
            self.obs.event("request/evict", request_id=req.request_id,
                           slot=slot, reason=reason,
                           tokens_discarded=len(state.generated))
            self.obs.counter("serve/evictions")
            self.slots[slot] = None
            self._positions[slot] = self.executor.scratch_position
            self._requeue(req)
        return req

    def run(self, max_iters: int = 100_000) -> Dict[int, RequestState]:
        """Step until drained (or ``max_iters``) — same truncation contract
        as ``ServingEngine.run``: a cap expiry warns, bumps
        ``serve/truncated`` by the pending count, and leaves unfinished
        requests queued/in-flight for a later ``run()``/``step()``."""
        it = 0
        while self.pending() and it < max_iters:
            self.step()
            it += 1
        pendings = len(self._heap) + sum(s is not None for s in self.slots)
        if pendings:
            warnings.warn(
                f"Scheduler.run hit max_iters={max_iters} with "
                f"{pendings} request(s) still pending; returned results "
                "are truncated", RuntimeWarning, stacklevel=2)
            self.obs.counter("serve/truncated", pendings)
        return self.finished

    # -- internals ------------------------------------------------------------
    def _tier_features(self, req: Request) -> Optional[int]:
        """The feature budget certified for this request's tier (None when
        tiers are not in play)."""
        if req.accuracy_tier is None or not self.accuracy_tiers:
            return None
        return self.executor.tier_features(
            self.accuracy_tiers[req.accuracy_tier])

    def _sample(self, logits: jax.Array, ids: np.ndarray, temperature,
                lanes: int) -> np.ndarray:
        """Sample every row of ``logits`` ``[n, V]`` on its request's key
        stream (``ids``: ``[2, n]`` request ids and token indices) in one
        ``sample_token`` call, then read the ``[n]`` tokens back in one
        device-to-host read. ``lanes`` rows are busy; the rest are
        ignored."""
        with self.obs.span("sample", lanes=lanes):
            # resolved at call time: a patched module-level sample_token
            # sees every lane of every step
            sampled = sample_token(logits, _lane_keys(self._base_key, ids),
                                   temperature)
        self.obs.counter("serve/sampled_lanes", lanes)
        with self.obs.span("fetch"):
            toks = np.asarray(sampled)
        self.obs.counter("serve/host_syncs")
        return toks

    def _requeue(self, request: Request) -> None:
        rid = request.request_id
        heapq.heappush(self._heap,
                       (-int(request.priority), self._seq_of[rid], request))
        # queue-age accounting restarts from the requeue (the original
        # submit time still anchors TTFT via the state's t_enqueue)
        self._t_submit.setdefault(rid, self.obs.now())
        self.obs.gauge("serve/queue_depth", len(self._heap))

    def _free_slots(self) -> List[int]:
        return [i for i, s in enumerate(self.slots) if s is None]

    def _admit_phase(self, info: StepInfo) -> None:
        free = self._free_slots()
        budget = (len(free) if self.max_admits_per_step is None
                  else min(self.max_admits_per_step, len(free)))
        while free and self._heap and budget > 0:
            slot = free.pop(0)
            _, _, req = heapq.heappop(self._heap)
            budget -= 1
            try:
                finished_at_admit = self._admit_one(slot, req, info)
            except Exception:
                # a failed prefill must not lose the popped request: put it
                # back at its original queue position before the recovery
                # path (or the caller) sees the exception
                self._requeue(req)
                raise
            if finished_at_admit:
                # hand the lane back for the next queued request this
                # same admission pass (it never decoded)
                free.insert(0, slot)
        if self._heap:
            oldest = min(self._t_submit.get(r.request_id, info.t_start)
                         for _, _, r in self._heap)
            self.obs.gauge("serve/queue_age_s", self.obs.now() - oldest)
        else:
            self.obs.gauge("serve/queue_age_s", 0.0)
        self.obs.gauge("serve/slots_occupied",
                       sum(s is not None for s in self.slots))

    def _admit_one(self, slot: int, req: Request, info: StepInfo) -> bool:
        """Prefill ``req`` into ``slot``. Returns True if it finished at
        admission (EOS/max_new_tokens=1/cache-filling prompt) — the lane
        is then still free."""
        rid = req.request_id
        t = len(req.prompt)
        tb = self.executor.bucket_for(t)
        attempt = self._attempts.get(rid, 0) + 1
        self._attempts[rid] = attempt
        tier_features = self._tier_features(req)
        with self.obs.span("admit", request_id=rid, slot=slot, bucket=tb,
                           attempt=attempt):
            self.obs.event("request/admit", request_id=rid, slot=slot,
                           bucket=tb, attempt=attempt,
                           accuracy_tier=req.accuracy_tier,
                           tier_features=tier_features)
            with self.obs.span("prefill", request_id=rid, bucket=tb,
                               prompt_len=t):
                logits, cache1, _ = self.executor.prefill(req.prompt)
                self.executor.splice(slot, cache1)
        t_enqueue = self._t_submit.pop(rid, None)
        if t_enqueue is None:
            t_enqueue = self.obs.now()
        state = RequestState(request=req, slot=slot, position=t,
                             t_enqueue=t_enqueue, admissions=attempt,
                             tier_features=tier_features)
        info.admitted.append(rid)
        # first generated token from the LAST REAL prefill logit, sampled
        # on the request's own key stream (token index 0)
        tok_i = int(self._sample(logits[:, t - 1],
                                 np.array([[rid], [0]], np.uint32),
                                 req.temperature, 1)[0])
        state.generated.append(tok_i)
        state.t_first_token = self.obs.now()
        state.t_tokens.append(state.t_first_token)
        info.new_tokens += 1
        self.obs.histogram("serve/ttft_s",
                           state.t_first_token - state.t_enqueue)
        self.obs.gauge("serve/queue_depth", len(self._heap))
        hit_eos = req.eos_token is not None and tok_i == req.eos_token
        if (hit_eos or len(state.generated) >= req.max_new_tokens
                or t >= self.max_len - 1):
            state.done = True
            state.t_done = self.obs.now()
            self._finish(state, "eos" if hit_eos else (
                "max_new_tokens"
                if len(state.generated) >= req.max_new_tokens
                else "cache_full"), info)
            return True
        self._tokens[slot, 0] = tok_i
        self._positions[slot] = t
        self.slots[slot] = state
        return False

    def _decode_phase(self, info: StepInfo) -> None:
        active = [s for s in self.slots if s is not None]
        info.active = len(active)
        if not active:
            return
        t_step = self.obs.now()
        with self.obs.span("decode/step", active=len(active)):
            logits = self.executor.decode(jnp.asarray(self._tokens),
                                          jnp.asarray(self._positions))
            # every lane is sampled (idle ones on a dummy key, their
            # tokens ignored), so the shapes never change
            ids = np.zeros((2, self.num_slots), np.uint32)
            temps = np.zeros((self.num_slots,), np.float32)
            for state in active:
                ids[:, state.slot] = (state.request.request_id,
                                      len(state.generated))
                temps[state.slot] = state.request.temperature
            # one float when the busy lanes agree: all-greedy compiles
            # only the argmax
            shared = {s.request.temperature for s in active}
            temperature = float(shared.pop()) if len(shared) == 1 else temps
            toks = self._sample(logits[:, 0], ids, temperature, len(active))
            t_tok = self.obs.now()
            for state in list(active):
                i = state.slot
                req = state.request
                tok = int(toks[i])
                state.generated.append(tok)
                self.obs.histogram("serve/inter_token_s",
                                   t_tok - state.t_tokens[-1])
                state.t_tokens.append(t_tok)
                state.position += 1
                info.new_tokens += 1
                self._tokens[i, 0] = tok
                self._positions[i] = state.position
                hit_eos = req.eos_token is not None and tok == req.eos_token
                if (len(state.generated) >= req.max_new_tokens or hit_eos
                        or state.position >= self.max_len - 1):
                    state.done = True
                    state.t_done = self.obs.now()
                    self._finish(state, "eos" if hit_eos else (
                        "max_new_tokens"
                        if len(state.generated) >= req.max_new_tokens
                        else "cache_full"), info)
                    self.slots[i] = None
                    self._positions[i] = self.executor.scratch_position
        dur = self.obs.now() - t_step
        if self.straggler_monitor is not None:
            self.straggler_monitor.record(self._step_idx, dur)
        self.obs.histogram("serve/token_latency_s", dur)
        self.obs.counter("serve/tokens_generated", len(active))
        self.obs.gauge("serve/slots_occupied",
                       sum(s is not None for s in self.slots))
        self.obs.tick_drift()

    def _recover(self, info: StepInfo, cause: str) -> None:
        """Respawn after a failed step: re-queue every in-flight request,
        reset the decode cache, continue. Requests replay from their
        prompts and regenerate identical tokens (per-request keys)."""
        requeued = []
        for i, state in enumerate(self.slots):
            if state is None:
                continue
            req = state.request
            requeued.append(req.request_id)
            info.evicted.append(req.request_id)
            self.slots[i] = None
            self._requeue(req)
            self.obs.event("request/evict", request_id=req.request_id,
                           slot=i, reason="restart",
                           tokens_discarded=len(state.generated))
        self.executor.reset_cache()
        self._tokens[:] = 0
        self._positions[:] = self.executor.scratch_position
        info.restarted = True
        self.obs.counter("serve/restarts")
        self.obs.event("serve/restart", cause=cause,
                       restart=self.restarts, requeued=requeued)
        self.obs.gauge("serve/slots_occupied", 0)

    def _finish(self, state: RequestState, reason: str,
                info: StepInfo) -> None:
        req = state.request
        state.finish_reason = reason
        self.finished[req.request_id] = state
        info.finished.append(req.request_id)
        n_tok = len(state.generated)
        self.obs.event("request/finish", request_id=req.request_id,
                       slot=state.slot, tokens=n_tok, reason=reason)
        wall = state.t_done - state.t_enqueue
        if wall > 0:
            self.obs.histogram("serve/tokens_per_s", n_tok / wall)
