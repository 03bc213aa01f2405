"""Scheduler: p90, over the requests due in the window, of the wait from a
request's due time to the start of its prefill call (host clock). A request
whose prefill has not started when the window closes counts at its age."""
from harness import stats


def read(run):
    waits = []
    for r in run.due_in_window():
        start = r["prefill_start"]
        end = start if start is not None and start <= run.t_close \
            else run.t_close
        waits.append(end - r["due"])
    return stats.percentile(waits, 90)
