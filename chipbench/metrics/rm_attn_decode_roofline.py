"""Kernels: the RM featurize kernel of the decode step
(``rm_feature_fused_pallas``, one launch a layer, the new queries and keys
of the busy lanes): the least time of its launches in the traced window,
the larger of counted FLOPs over peak and counted bytes over bandwidth
(costs/rm_attn_decode.py), over their device time, in percent. Which bound
held is printed with the run."""
import sys

KERNEL = "rm_feature_fused_pallas"


def read(run):
    if run.trace is None:
        return None
    seconds, count = run.trace.op_time(KERNEL)
    calls = run.calls_in_window("decode")
    if not count or not calls:
        return None
    cost = run.cost("rm_attn_decode")
    layers = run.config["model"]["num_hidden_layers"]
    pf, bw = run.peaks["bf16_flops_per_s"], run.peaks["hbm_bytes_per_s"]
    lanes = [len(c["contexts"]) for c in calls]
    t_flops = sum(cost.flops(run.config, n) for n in lanes) / pf
    t_bytes = sum(cost.bytes_moved(run.config, n) for n in lanes) / bw
    print(f"[metric] {KERNEL}: {count} launches, compute bound "
          f"{layers * t_flops:.6g} s, memory bound {layers * t_bytes:.6g} s",
          file=sys.stderr)
    least = layers * sum(max(cost.flops(run.config, n) / pf,
                             cost.bytes_moved(run.config, n) / bw)
                         for n in lanes)
    return 100.0 * least / seconds
