"""Executor: device time of the prefill programs in the traced window over
the real prompt tokens of the prefill calls in it, in microseconds."""


def read(run):
    if run.trace is None:
        return None
    seconds, count = run.trace.module_time("_prefill_compiled")
    tokens = sum(c["tokens"] for c in run.calls_in_window("prefill"))
    if not count or not tokens:
        return None
    return seconds * 1e6 / tokens
