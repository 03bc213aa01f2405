"""Model: counted model FLOPs of the real prompt tokens prefilled in the
traced window (costs/model_forward.py) over the prefill programs' device
time times the chip's peak, in percent."""


def read(run):
    if run.trace is None:
        return None
    seconds, count = run.trace.module_time("_prefill_compiled")
    calls = run.calls_in_window("prefill")
    if not count or not calls:
        return None
    mf = run.cost("model_forward")
    flops = sum(mf.prefill(run.config, c["tokens"]) for c in calls)
    return 100.0 * flops / (seconds * run.peaks["bf16_flops_per_s"])
