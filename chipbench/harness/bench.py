"""Run one cell once: set-up, the measured window, the check, the result.

The window drives the served path as a user would: ``Scheduler.submit`` and
``Scheduler.step`` on the program's ``StepExecutor``. The harness only
wraps the executor instance's ``prefill``, ``splice`` and ``decode`` to
record when each call starts and ends on the host, and names them for the
profiler (``bench.prefill`` ...), so that device idle time can be put down
to what the host was doing.
"""
from __future__ import annotations

import gc
import math
import os
import shutil
import sys
import tempfile
import time
from typing import Any, Dict, List, Optional

import numpy as np

from harness import check, loader, stats, traffic as traffic_mod

SRC = loader.ROOT / "src"
CACHE_DIR = loader.ROOT / ".xla-cache"
WARM_ID0 = 1 << 30          # request ids of the warm-up, apart from traffic


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class CompileMeter:
    """Backend-compile seconds and persistent-cache hits and misses, from
    JAX's monitoring events (copied from ``chip_smoke.py``)."""

    def __init__(self, jax):
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        self.compiles = 0

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += secs
                self.compiles += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def mark(self):
        return (self.seconds, self.hits, self.misses, self.compiles)

    def since(self, mark) -> Dict[str, float]:
        s, h, m, c = mark
        return {"compile_s": self.seconds - s, "hits": self.hits - h,
                "misses": self.misses - m, "compiles": self.compiles - c}


def setup_jax():
    """Import jax with the persistent compilation cache in
    ``$JAX_COMPILATION_CACHE_DIR`` where that is set, else at the fixed
    path ``<checkout>/.xla-cache``; every program cached."""
    cache = os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(CACHE_DIR))
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))
    import jax

    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def find_chip(jax, chips: int):
    """The devices of the cell, or None where JAX finds no accelerator or
    fewer chips than the cell asks for."""
    devs = jax.devices()
    if devs[0].platform == "cpu" or len(devs) < chips:
        return None
    return devs


def program_config(ref, config: Dict):
    """The program's ``ModelConfig``, checked against the configuration
    file: a run that departs from what the file states is refused. The
    reference says which program fields its family's published keys fix
    (``ref.program_fields``); the file's attention mode, precision and RM
    settings are checked as they are written."""
    from repro.configs import get_config

    p = config["program"]
    cfg = get_config(p["arch"], smoke=bool(p["smoke"]),
                     attention_mode=config["attention"])
    want = dict(ref.program_fields(config),
                attention_mode=config["attention"],
                param_dtype=config["precision"]["param_dtype"],
                compute_dtype=config["precision"]["compute_dtype"])
    if config["attention"] == "rm":
        for k, v in config["rm"].items():
            want[f"rm.{k}"] = v
    for key, value in want.items():
        got = cfg
        for part in key.split("."):
            got = getattr(got, part)
        if got != value:
            raise SystemExit(f"program config {key} = {got!r}, but the "
                             f"configuration file states {value!r}")
    return cfg


def make_params(jax, ref, config: Dict, cfg, seed: int):
    """The reference's weights from the seed, made on the device in one
    jitted call, and the same arrays in the program's parameter tree."""
    from repro.models import init_model

    words = traffic_mod.seed_words(seed, 2).astype(np.uint32)
    key = jax.random.wrap_key_data(jax.numpy.asarray(words))
    weights = jax.jit(lambda k: ref.init(config, k))(key)
    jax.block_until_ready(weights)
    probe = jax.eval_shape(lambda: init_model(cfg, jax.random.PRNGKey(0)))
    flat, treedef = jax.tree_util.tree_flatten_with_path(probe)
    leaves = []
    for path, leaf in flat:
        name = ref.program_name([getattr(p, "key", str(p)) for p in path])
        w = weights[name]
        if w.shape != leaf.shape or w.dtype != leaf.dtype:
            raise SystemExit(f"weight {name}: {w.shape} {w.dtype}, program "
                             f"wants {leaf.shape} {leaf.dtype}")
        leaves.append(w)
    if len({id(x) for x in leaves}) != len(weights):
        raise SystemExit("program and reference weights do not pair up")
    return weights, jax.tree_util.tree_unflatten(treedef, leaves)


class Recorder:
    """Host times of the executor's calls, and what each call worked on."""

    def __init__(self, jax, sched):
        self.calls: List[Dict[str, Any]] = []
        self.prefill_start: Dict[int, float] = {}
        ex = sched.executor
        annotate = jax.profiler.TraceAnnotation

        def wrap(kind, fn):
            def call(*args, **kwargs):
                info = {"kind": kind, "t0": time.perf_counter()}
                if kind == "prefill":
                    info["tokens"] = len(args[0])
                    self.prefill_start[id(args[0])] = info["t0"]
                elif kind == "decode":
                    lanes = [i for i, s in enumerate(sched.slots)
                             if s is not None]
                    info["contexts"] = [int(sched._positions[i]) + 1
                                        for i in lanes]
                with annotate(f"bench.{kind}"):
                    out = fn(*args, **kwargs)
                info["t1"] = time.perf_counter()
                self.calls.append(info)
                return out
            return call

        for kind in ("prefill", "splice", "decode"):
            setattr(ex, kind, wrap(kind, getattr(ex, kind)))


def warm_up(sched, traffic: Dict, vocab: int, Request) -> None:
    """Compile what this cell's traffic uses and nothing else: the prefill
    of each bucket its prompts can fall in, the decode step with every lane
    busy, and the per-bucket and per-lane sampling slices."""
    ex = sched.executor
    lo, hi = traffic["prompt"]["min"], traffic["prompt"]["max"]
    lengths = sorted({min(b, hi) for b in ex.buckets
                      if ex.bucket_for(lo) <= b <= ex.bucket_for(hi)})
    n = max(len(lengths), sched.num_slots)
    rng = np.random.default_rng(0)
    for i in range(n):
        sched.submit(Request(request_id=WARM_ID0 + i,
                             prompt=rng.integers(0, vocab, lengths[
                                 i % len(lengths)]).astype(np.int32),
                             max_new_tokens=3))
    while sched.pending():
        sched.step()


def _open_loop(jax, sched, plan, t_lead: float, seconds: float):
    """Submit each request when it is due; step while there is work. The
    window opens at the first loop turn ``t_lead`` after the traffic
    starts, and closes at the end of the step that reaches its length."""
    annotate = jax.profiler.TraceAnnotation
    t0 = time.perf_counter()
    due = [t0 + a.t for a, _ in plan]
    submitted: List[float] = []
    t_open = t_end = None
    nxt = 0
    window = annotate("bench.window")
    while True:
        now = time.perf_counter()
        if t_open is None and now >= t0 + t_lead:
            t_open, t_end = now, now + seconds
            window.__enter__()
        while nxt < len(plan) and due[nxt] <= now:
            sched.submit(plan[nxt][1])
            submitted.append(time.perf_counter())
            nxt += 1
        if t_end is not None and now >= t_end:
            break
        if sched.pending():
            with annotate("bench.step"):
                sched.step()
        else:
            wake = t_end if t_end is not None else t0 + t_lead
            if nxt < len(plan):
                wake = min(wake, due[nxt])
            with annotate("bench.wait"):
                time.sleep(max(0.0, wake - time.perf_counter()))
    t_close = time.perf_counter()
    window.__exit__(None, None, None)
    if nxt == len(plan):
        raise SystemExit("open traffic ran out of planned requests")
    return due, submitted, t_open, t_end, t_close


def _batch_loop(jax, sched, plan, queue_min: int, seconds: float):
    """Keep ``queue_min`` requests waiting; the window opens once every
    slot holds a request."""
    annotate = jax.profiler.TraceAnnotation
    nxt = 0

    def top_up():
        nonlocal nxt
        while sched.queue_depth < queue_min:
            if nxt == len(plan):
                raise SystemExit("batch traffic ran out of planned requests")
            sched.submit(plan[nxt][1])
            nxt += 1

    top_up()
    while any(s is None for s in sched.slots):
        top_up()
        sched.step()
    t_open = time.perf_counter()
    window = annotate("bench.window")
    window.__enter__()
    while True:
        top_up()
        with annotate("bench.step"):
            sched.step()
        if time.perf_counter() >= t_open + seconds:
            break
    t_close = time.perf_counter()
    window.__exit__(None, None, None)
    return t_open, t_open + seconds, t_close


def run_cell(cell: Dict, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_chip: bool = True,
             control: bool = False,
             keep_trace: Optional[str] = None) -> Optional[Dict]:
    """One run of one cell. Returns the result, or None where there is no
    chip for it."""
    jax = setup_jax()
    devs = find_chip(jax, cell["chips"])
    if devs is None:
        if require_chip:
            log(f"no accelerator with {cell['chips']} chip(s): "
                f"{jax.devices()}")
            return None
        devs = jax.devices()
    dev = devs[0]
    peaks = loader.peaks(dev.device_kind) if require_chip else None
    meter = CompileMeter(jax)
    from repro.serve import Request, Scheduler

    config, tspec = cell["config"], cell["traffic"]
    traffic_mod.validate(tspec)
    srv = config["serving"]
    if traffic_mod.longest(tspec) >= srv["max_len"]:
        raise SystemExit("traffic's longest request does not fit max_len")
    ref = loader.reference(config)
    split = {"import_s": time.perf_counter() - t_start}

    mark, t = meter.mark(), time.perf_counter()
    cfg = program_config(ref, config)
    weights, params = make_params(jax, ref, config, cfg, seed)
    split["weights_s"] = time.perf_counter() - t
    split["weights_compile"] = meter.since(mark)

    mark, t = meter.mark(), time.perf_counter()
    sched = Scheduler(cfg, params, num_slots=srv["num_slots"],
                      max_len=srv["max_len"], rng_seed=0,
                      buckets=srv["buckets"])
    rec = Recorder(jax, sched)
    warm_up(sched, tspec, cfg.vocab_size, Request)
    split["warmup_s"] = time.perf_counter() - t
    split["warmup_compile"] = meter.since(mark)

    # the whole plan, prompts included, is made before the window
    if tspec["kind"] == "open":
        n_plan = int(tspec["rate"] * (tspec["lead_s"] + seconds) * 1.5) + 64
    else:
        n_plan = 256 + 16 * int(seconds)
    plan = []
    for a in traffic_mod.arrivals(tspec, seed, n_plan):
        plan.append((a, Request(request_id=a.request_id,
                                prompt=traffic_mod.prompt_for(
                                    a, cfg.vocab_size, seed),
                                max_new_tokens=a.max_new_tokens,
                                temperature=0.0)))
    trace_dir = None
    if trace:
        trace_dir = keep_trace or tempfile.mkdtemp(prefix="chipbench-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    # set-up's garbage is collected now, not by a pass inside the window
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start
    split["setup_s"] = setup_s
    log("setup " + " ".join(f"{k}={v:.3f}" if isinstance(v, float)
                            else f"{k}={v}" for k, v in split.items()))

    mark = meter.mark()
    n_calls0 = len(rec.calls)
    if tspec["kind"] == "open":
        due, submitted, t_open, t_end, t_close = _open_loop(
            jax, sched, plan, float(tspec["lead_s"]), seconds)
    else:
        t_open, t_end, t_close = _batch_loop(
            jax, sched, plan, int(tspec["queue_min"]), seconds)
        due, submitted = [None] * len(plan), []
    in_window = meter.since(mark)
    if in_window["compiles"]:
        log(f"WARNING: {in_window['compiles']} compilation(s) inside the "
            "window")
    memory_peak = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
    if trace:
        jax.profiler.stop_trace()

    states = dict(sched.finished)
    states.update({s.request.request_id: s for s in sched.slots
                   if s is not None})
    requests = []
    for i, ((a, req), d) in enumerate(zip(plan, due)):
        st = states.get(a.request_id)
        requests.append({
            "rid": a.request_id, "due": d, "prompt_len": a.prompt_len,
            "submit": submitted[i] if i < len(submitted) else None,
            "prefill_start": rec.prefill_start.get(id(req.prompt)),
            "first": st.t_first_token if st else None,
            "tokens": list(st.t_tokens) if st else [],
            "done": st.t_done if st and st.done else None,
            "lane": st.slot if st else None,
            "finish": st.finish_reason if st else None,
            "generated": list(st.generated) if st else [],
            "prompt": req.prompt,
        })
    run = Run(cell=cell, config=config, peaks=peaks, requests=requests,
              calls=rec.calls[n_calls0:], t_open=t_open, t_end=t_end,
              t_close=t_close)
    del sched, rec, params, states
    gc.unfreeze()
    gc.collect()

    if trace:
        from harness import tracefile

        files = sorted(tracefile.find(trace_dir))
        run.trace = tracefile.reduce(tracefile.load(files[-1]))
        if keep_trace is None:
            shutil.rmtree(trace_dir, ignore_errors=True)

    result = check.run_check(jax, ref, config, weights, run, seed,
                             control=control)
    del weights
    metrics = (end_to_end(run, setup_s) if not trace
               else per_layer(run))
    n_due, failed = run.attempted()
    out = {
        "correct": bool(result["correct"]),
        "attempted": n_due,
        "failed": failed,
        "metrics": metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devs),
                   "memory_peak_bytes": int(memory_peak)},
    }
    if trace:
        out["device"]["busy_s"] = run.trace.busy_s
        out["device"]["window_s"] = run.trace.window_s
        out["breakdown"] = run.trace.breakdown()
    out["diagnostics"] = {**split, "window_s": t_close - t_open,
                          "backlog_at_close": run.backlog(),
                          "compiles_in_window": in_window["compiles"],
                          "generator_late_p90_s": run.generator_late_p90()}
    out["compared"] = result["compared"]
    return out


class Run:
    """What one run recorded, as the per-layer readers see it."""

    def __init__(self, **kw):
        self.trace = None
        self.__dict__.update(kw)

    def cost(self, name: str):
        return loader.cost(name)

    def in_window(self, t: Optional[float]) -> bool:
        return t is not None and self.t_open <= t < self.t_close

    def due_in_window(self) -> List[Dict]:
        return [r for r in self.requests
                if r["due"] is not None and self.t_open <= r["due"]
                < self.t_end]

    def calls_in_window(self, kind: str) -> List[Dict]:
        return [c for c in self.calls
                if c["kind"] == kind and self.in_window(c["t0"])]

    def token_times(self) -> List[List[float]]:
        return [r["tokens"] for r in self.requests if r["tokens"]]

    def attempted(self):
        """Requests the window handled, and how many of them failed: for
        open traffic those due in it, for batch those that emitted a token
        in it. A request fails when it ends for another reason than
        reaching its token count."""
        if self.requests and self.requests[0]["due"] is not None:
            handled = self.due_in_window()
        else:
            handled = [r for r in self.requests
                       if any(self.t_open < t <= self.t_close
                              for t in r["tokens"])]
        failed = sum(1 for r in handled if r["finish"] not in
                     (None, "max_new_tokens"))
        return len(handled), failed

    def backlog(self) -> int:
        """Requests due by the close whose prefill had not started."""
        return sum(1 for r in self.requests
                   if r["due"] is not None and r["due"] < self.t_close
                   and (r["prefill_start"] is None
                        or r["prefill_start"] > self.t_close))

    def generator_late_p90(self) -> Optional[float]:
        """How late requests reached ``submit``, p90: they are submitted
        between steps, so this is the wait for the step in progress."""
        late = [r["submit"] - r["due"] for r in self.due_in_window()
                if r["submit"] is not None]
        return stats.percentile(late, 90) if late else None


def end_to_end(run: Run, setup_s: float) -> Dict[str, Dict]:
    out = {}
    times = run.token_times()
    for m in run.cell["end_to_end"]:
        name = m["name"]
        if name == "setup_s":
            v = setup_s
        elif name == "ttft_p90_s":
            v = stats.percentile(stats.ttft_due(
                run.requests, run.t_open, run.t_end), 90)
        elif name == "itl_p95_s":
            v = stats.percentile(stats.inter_token_gaps(
                times, run.t_open, run.t_close), 95)
        elif name == "output_tokens_per_s":
            v = stats.rate(stats.tokens_in_window(
                times, run.t_open, run.t_close), run.t_open, run.t_close)
        else:
            raise SystemExit(f"no definition of end-to-end metric {name}")
        if v is not None and math.isfinite(v):
            out[name] = {"value": float(v), "unit": m["unit"]}
    return out


def per_layer(run: Run) -> Dict[str, Dict]:
    out = {}
    for m in run.cell["per_layer"]:
        v = loader.metric_reader(m["name"]).read(run)
        if v is not None and math.isfinite(v):
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out
