"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b --smoke \
        --steps 50 --batch 4 --seq 128

On hardware, the same entrypoint builds the production mesh and shards the
run; on this CPU container use --smoke (reduced config) for real execution,
or the dry-run for full-scale lowering.
"""
from __future__ import annotations

import argparse

import jax

from repro.configs import get_config, list_archs
from repro.data.synthetic import SyntheticLMDataset
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.train.steps import TrainHyper
from repro.train.trainer import Trainer


def main():
    from repro.common.env import use_compile_cache

    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b", choices=list_archs())
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-runnable)")
    ap.add_argument("--attention-mode", default=None,
                    choices=[None, "exact", "rm"])
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--mesh", default="none",
                    choices=["none", "host", "single", "multi"])
    ap.add_argument("--model-parallel", type=int, default=1,
                    help="TP size for --mesh host")
    ap.add_argument("--trace-out", default=None, metavar="FILE",
                    help="stream a JSONL trace of the train/step "
                         "spans (inspect with python -m repro.obs)")
    ap.add_argument("--metrics-out", default=None, metavar="FILE",
                    help="write the metrics snapshot (step-time histogram, "
                         "loss gauge) as JSON")
    ap.add_argument("--drift-every", type=int, default=0, metavar="N",
                    help="run the online (eps, delta) Gram-drift check "
                         "every N train steps (0 = off; rm attention only)")
    from repro.launch.budget import add_budget_args, apply_budget_selection

    add_budget_args(ap)
    args = ap.parse_args()

    cfg = get_config(args.arch, smoke=args.smoke,
                     attention_mode=args.attention_mode)
    cfg, _decision = apply_budget_selection(cfg, args, tag="train")
    if cfg.frontend != "none":
        raise SystemExit(
            f"{args.arch} needs modality inputs; use examples/train_lm.py "
            "with an LM arch, or the dry-run for full-scale lowering.")
    data = SyntheticLMDataset(vocab_size=cfg.vocab_size, seq_len=args.seq,
                              global_batch=args.batch)
    mesh = {
        "none": None,
        "host": lambda: make_host_mesh(args.model_parallel),
        "single": lambda: make_production_mesh(),
        "multi": lambda: make_production_mesh(multi_pod=True),
    }[args.mesh]
    mesh = mesh() if callable(mesh) else mesh
    hyper = TrainHyper(peak_lr=args.lr, warmup_steps=max(args.steps // 10, 5),
                       total_steps=args.steps, grad_accum=args.grad_accum)

    obs = None
    if args.trace_out or args.metrics_out or args.drift_every:
        from repro import obs as obs_mod

        drift = None
        if args.drift_every and cfg.attention_mode == "rm":
            from repro.core import ExponentialDotProductKernel

            rm = cfg.rm
            drift = obs_mod.DriftMonitor.for_estimator(
                ExponentialDotProductKernel(sigma2=rm.sigma2),
                cfg.resolved_head_dim, rm.num_features,
                estimator=rm.estimator, measure=rm.measure,
                # hold the monitored map to the SELECTED delta
                **({"delta": args.delta}
                   if args.delta is not None else {}))
        elif args.drift_every:
            print("[train] --drift-every ignored: attention mode is not "
                  "rm-family")
        obs = obs_mod.Obs(trace_path=args.trace_out, drift=drift,
                          drift_every=args.drift_every)

    trainer = Trainer(cfg, hyper, data, ckpt_dir=args.ckpt_dir, mesh=mesh,
                      obs=obs)
    trainer.train(args.steps)

    if obs is not None:
        if obs.drift is not None and obs.drift.last is not None:
            rep = obs.drift.last
            print(f"[train] drift: sup_err={rep.sup_err:.4f} vs "
                  f"eps({rep.num_features}, delta)={rep.eps_bound:.4f} "
                  f"[{'OK' if rep.ok else 'VIOLATION'}]")
        if args.metrics_out:
            obs.write_metrics(args.metrics_out)
            print(f"[train] wrote metrics -> {args.metrics_out}")
        obs.close()
        if args.trace_out:
            print(f"[train] wrote trace -> {args.trace_out}")


if __name__ == "__main__":
    main()
