"""Kernels: the fused causal RM attention kernel of prefill
(``rm_fused_attention_pallas``, one launch a layer): the least time of its
launches in the traced window, the larger of counted FLOPs over peak and
counted bytes over bandwidth (costs/rm_attn_prefill.py), over their device
time, in percent. Which bound held is printed with the run."""
import sys

KERNEL = "rm_fused_attention_pallas"


def read(run):
    if run.trace is None:
        return None
    seconds, count = run.trace.op_time(KERNEL)
    calls = run.calls_in_window("prefill")
    if not count or not calls:
        return None
    cost = run.cost("rm_attn_prefill")
    layers = run.config["model"]["num_hidden_layers"]
    pf, bw = run.peaks["bf16_flops_per_s"], run.peaks["hbm_bytes_per_s"]
    t_flops = sum(cost.flops(run.config, c["tokens"]) for c in calls) / pf
    t_bytes = sum(cost.bytes_moved(run.config, c["tokens"])
                  for c in calls) / bw
    print(f"[metric] {KERNEL}: {count} launches, compute bound "
          f"{layers * t_flops:.6g} s, memory bound {layers * t_bytes:.6g} s",
          file=sys.stderr)
    least = layers * sum(max(cost.flops(run.config, c["tokens"]) / pf,
                             cost.bytes_moved(run.config, c["tokens"]) / bw)
                         for c in calls)
    return 100.0 * least / seconds
