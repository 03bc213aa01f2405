"""Executor: the least time of one decode step, its counted bytes
(costs/decode_step.py) at the chip's HBM bandwidth (memory bound), over the
mean device time of the decode program in the traced window, in percent."""


def read(run):
    if run.trace is None:
        return None
    seconds, count = run.trace.module_time("_decode_compiled")
    calls = run.calls_in_window("decode")
    if not count or not calls:
        return None
    ds = run.cost("decode_step")
    least = sum(ds.step(run.config, c["contexts"]) for c in calls) \
        / run.peaks["hbm_bytes_per_s"]
    return 100.0 * (least / len(calls)) / (seconds / count)
