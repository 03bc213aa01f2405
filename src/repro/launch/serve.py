"""Serving launcher: batched requests through the continuous-batching engine.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b --smoke \
        --requests 8 --slots 4

``--scheduler`` picks the frontend — ``continuous`` (default) is the
continuous-batching Scheduler with per-step admission/eviction and
priority queues, ``bucketed`` the deprecated batch-synchronous engine;
``--arrival-trace`` replays a JSONL arrival trace (see
``repro.bench.loadgen``) open-loop through the continuous scheduler.
``--estimator`` picks the linear-attention feature family by registry name
(forwarded to ``get_config``, validated at engine construction);
``--data-parallel`` builds a host mesh and runs data-parallel decode with
replicated estimator params (DESIGN.md §10) — pair with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` on CPU.

Observability (docs/observability.md): ``--trace-out trace.jsonl`` streams
the request lifecycle and the scheduler's step spans as JSONL (summarize
or convert with ``python -m repro.obs``), ``--metrics-out metrics.json``
snapshots the TTFT / token-latency / tokens-per-sec histograms, and
``--drift-every N`` runs the online (eps, delta) Gram-drift check every N
decode iterations.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config, list_archs
from repro.models import init_model
from repro.serve import Request, Scheduler, ServingEngine


def make_engine(
    arch: str,
    *,
    smoke: bool = True,
    attention_mode: str | None = None,
    estimator: str | None = None,
    num_slots: int = 4,
    max_len: int = 128,
    mesh=None,
    seed: int = 0,
    obs=None,
    scheduler: str = "continuous",
    buckets=None,
    cfg=None,
    accuracy_tiers=None,
):
    """Config -> params -> serving frontend, with every override forwarded.

    ``scheduler`` picks the frontend: ``"continuous"`` (default) builds the
    continuous-batching :class:`~repro.serve.scheduler.Scheduler`;
    ``"bucketed"`` the legacy batch-synchronous ``ServingEngine``
    (deprecated, docs/serving.md). Both expose the same submit/run surface.

    ``cfg`` short-circuits the ``get_config`` resolution with an already-
    resolved config (the launcher uses this after budget selection rewrites
    ``cfg.rm``); ``accuracy_tiers`` maps tier names to feature-generation
    counts (continuous scheduler only, docs/adaptive.md).

    The regression this guards (tests/test_serve_engine.py): ``estimator``
    must reach ``get_config`` so the engine's up-front registry validation
    sees the requested family — silently serving the default "rm" estimator
    under a ``--estimator tensor_sketch`` launch is exactly the conformance
    drift the registry exists to prevent.
    """
    if cfg is None:
        cfg = get_config(arch, smoke=smoke, attention_mode=attention_mode,
                         estimator=estimator)
    if not cfg.causal:
        raise ValueError(f"{arch} is encoder-only; nothing to serve")
    params = init_model(cfg, jax.random.PRNGKey(seed))
    if scheduler == "continuous":
        return Scheduler(cfg, params, num_slots=num_slots, max_len=max_len,
                         rng_seed=seed, buckets=buckets, mesh=mesh, obs=obs,
                         accuracy_tiers=accuracy_tiers)
    if scheduler == "bucketed":
        if accuracy_tiers is not None:
            raise ValueError("accuracy tiers need the continuous "
                             "scheduler; the bucketed engine has no "
                             "per-request admission surface")
        return ServingEngine(cfg, params, num_slots=num_slots,
                             max_len=max_len, rng_seed=seed, buckets=buckets,
                             mesh=mesh, obs=obs)
    raise ValueError(f"unknown scheduler {scheduler!r}: expected "
                     "'continuous' or 'bucketed'")


def parse_tiers(spec: str):
    """``"low:1,standard:2,high:4"`` -> ``{"low": 1, ...}`` (CLI format)."""
    tiers = {}
    for part in spec.split(","):
        name, _, gens = part.partition(":")
        name = name.strip()
        if not name or not gens.strip().isdigit():
            raise SystemExit(
                f"[serve] bad --accuracy-tiers entry {part!r}: expected "
                "name:generations pairs like 'low:1,standard:2,high:4'")
        tiers[name] = int(gens)
    return tiers


def main(argv=None):
    from repro.common import env

    env.use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b", choices=list_archs())
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--attention-mode", default=None,
                    choices=[None, "exact", "rm"])
    ap.add_argument("--estimator", default=None,
                    help="feature-estimator registry name "
                         "(rm/tensor_sketch/ctr)")
    ap.add_argument("--data-parallel", action="store_true",
                    help="decode over a host mesh (DP slots, replicated "
                         "params)")
    ap.add_argument("--platform", default=None,
                    choices=["cpu", "gpu", "tpu"],
                    help="pin the jax platform before backend init "
                         "(repro.common.env.set_platform)")
    ap.add_argument("--host-devices", type=int, default=0,
                    help="expose N host CPU devices via XLA_FLAGS (for "
                         "--data-parallel on one machine)")
    ap.add_argument("--scheduler", default="continuous",
                    choices=["continuous", "bucketed"],
                    help="serving frontend: the continuous-batching "
                         "Scheduler (default) or the deprecated "
                         "batch-synchronous bucketed engine")
    ap.add_argument("--arrival-trace", default=None, metavar="FILE",
                    help="replay a JSONL arrival trace (repro.bench."
                         "loadgen format) open-loop instead of submitting "
                         "everything up front (continuous scheduler only)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--trace-out", default=None, metavar="FILE",
                    help="stream a JSONL trace of the request "
                         "lifecycle and the scheduler's step spans "
                         "(inspect with python -m repro.obs)")
    ap.add_argument("--metrics-out", default=None, metavar="FILE",
                    help="write the metrics snapshot (TTFT/latency/tok-s "
                         "histograms) as JSON")
    ap.add_argument("--drift-every", type=int, default=0, metavar="N",
                    help="run the online (eps, delta) Gram-drift check "
                         "every N decode iterations (0 = off; needs an "
                         "rm-family --attention-mode)")
    ap.add_argument("--accuracy-tiers", default=None, metavar="SPEC",
                    help="per-request accuracy tiers as name:generations "
                         "pairs, e.g. 'low:1,standard:2,high:4' "
                         "(continuous scheduler + rm attention; synthetic "
                         "requests cycle through the tiers)")
    from repro.launch.budget import add_budget_args, apply_budget_selection

    add_budget_args(ap)
    args = ap.parse_args(argv)

    # platform knobs must land before the first device query initializes
    # the backend (repro.common.env docstring)
    if args.host_devices:
        env.set_host_device_count(args.host_devices)
    if args.platform:
        env.set_platform(args.platform)

    mesh = None
    if args.data_parallel:
        from repro.launch.mesh import make_host_mesh

        mesh = make_host_mesh()
        print(f"[serve] mesh {dict(mesh.shape)} over {len(jax.devices())} "
              "devices")

    # resolve the config ONCE: the budget selection (when requested)
    # rewrites cfg.rm, and the drift monitor + engine must both see the
    # selected budget, not the arch default
    cfg = get_config(args.arch, smoke=args.smoke,
                     attention_mode=args.attention_mode,
                     estimator=args.estimator)
    cfg, _decision = apply_budget_selection(cfg, args, tag="serve")

    tiers = parse_tiers(args.accuracy_tiers) if args.accuracy_tiers \
        else None
    if tiers and _decision is not None:
        # tiers split the budget into max(generations) equal blocks; round
        # the selected D UP to the next multiple (eps_at only tightens)
        import dataclasses

        gmax = max(tiers.values())
        d = cfg.rm.num_features
        if d % gmax:
            d += gmax - d % gmax
            cfg = dataclasses.replace(cfg, rm=dataclasses.replace(
                cfg.rm, num_features=d)).validate()
            print(f"[serve] rounded D up to {d} (multiple of {gmax} "
                  "tier generations)")

    obs = None
    if args.trace_out or args.metrics_out or args.drift_every:
        from repro import obs as obs_mod

        drift = None
        if args.drift_every:
            # watch a map drawn exactly like the deployed attention
            # featurizer: same estimator family, measure and budget D
            if cfg.attention_mode == "rm":
                from repro.core import ExponentialDotProductKernel

                rm = cfg.rm
                drift = obs_mod.DriftMonitor.for_estimator(
                    ExponentialDotProductKernel(sigma2=rm.sigma2),
                    cfg.resolved_head_dim, rm.num_features,
                    estimator=rm.estimator, measure=rm.measure,
                    # the monitor holds the map to the SELECTED delta
                    **({"delta": args.delta}
                       if args.delta is not None else {}))
            else:
                print("[serve] --drift-every ignored: attention mode is "
                      "not rm-family")
        obs = obs_mod.Obs(trace_path=args.trace_out, drift=drift,
                          drift_every=args.drift_every)

    engine = make_engine(
        args.arch, num_slots=args.slots, max_len=args.max_len,
        mesh=mesh, obs=obs, scheduler=args.scheduler, cfg=cfg,
        accuracy_tiers=tiers,
    )
    t0 = time.time()
    if args.arrival_trace:
        if args.scheduler != "continuous":
            raise SystemExit("--arrival-trace needs --scheduler continuous")
        from repro.bench import loadgen

        arrivals = loadgen.load_trace(args.arrival_trace)
        raw = loadgen.run_load(engine, arrivals)
        done = raw["finished"]
        print(f"[serve] replayed {len(arrivals)} arrivals from "
              f"{args.arrival_trace} ({raw['truncated']} truncated)")
    else:
        rng = np.random.default_rng(0)
        tier_names = sorted(tiers) if tiers else None
        for i in range(args.requests):
            prompt = rng.integers(0, cfg.vocab_size,
                                  size=int(rng.integers(4, 24)))
            # synthetic load cycles through the configured tiers so every
            # tier's admission path (and tier_features certification) runs
            tier = tier_names[i % len(tier_names)] if tier_names else None
            engine.submit(Request(request_id=i, prompt=prompt,
                                  max_new_tokens=args.max_new,
                                  accuracy_tier=tier))
        done = engine.run()
        if tier_names:
            for rid in sorted(done):
                s = done[rid]
                if s.tier_features is not None:
                    print(f"  req {rid}: tier="
                          f"{s.request.accuracy_tier} certified at "
                          f"D={s.tier_features}")
    wall = time.time() - t0
    toks = sum(len(s.generated) for s in done.values())
    print(f"[serve] {len(done)} requests, {toks} tokens in {wall:.1f}s "
          f"({toks / wall:.1f} tok/s aggregate)")
    for rid in sorted(done):
        s = done[rid]
        ttft = (s.t_first_token - s.t_enqueue) if s.t_first_token else None
        print(f"  req {rid}: {len(s.generated)} tokens, "
              f"ttft={ttft:.2f}s" if ttft else f"  req {rid}")

    if obs is not None:
        snap = obs.metrics.snapshot()
        hists = snap.get("histograms", {})

        def _h(name):
            return hists.get(name, {})

        ttft_s, tok_s = _h("serve/ttft_s"), _h("serve/tokens_per_s")
        if ttft_s:
            print(f"[serve] ttft p50={ttft_s['p50']:.3f}s "
                  f"p99={ttft_s['p99']:.3f}s | per-request tok/s "
                  f"p50={tok_s.get('p50', float('nan')):.1f}")
        if obs.drift is not None and obs.drift.last is not None:
            rep = obs.drift.last
            print(f"[serve] drift: sup_err={rep.sup_err:.4f} vs "
                  f"eps({rep.num_features}, delta)={rep.eps_bound:.4f} "
                  f"[{'OK' if rep.ok else 'VIOLATION'}] "
                  f"({obs.drift.checks} checks, "
                  f"{obs.drift.violations} violations)")
        if args.metrics_out:
            obs.write_metrics(args.metrics_out)
            print(f"[serve] wrote metrics -> {args.metrics_out}")
        obs.close()
        if args.trace_out:
            print(f"[serve] wrote trace -> {args.trace_out}")


if __name__ == "__main__":
    main()
