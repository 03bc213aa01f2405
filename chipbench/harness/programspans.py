"""The program's own spans in a profiler trace, and the device's idle time
put down to them.

The program names each of its spans ``repro.<name>`` on the profiler's host
plane, the plane of the harness's ``bench.*`` spans, so they share a clock
with the device's operations. Its serving scheduler opens, per tick:

    repro.step                     the whole tick
      repro.admit > repro.prefill  an admission (prefill call and splice)
      repro.sample, repro.fetch    the admitted request's first token
      repro.decode/step            the batched decode call, then per lane:
        repro.sample               key, logit slice, sampling dispatch
        repro.fetch                the token's device-to-host read

``load`` reads what ``tracefile.load`` reads, and these spans as a
``"program"`` list of ``[name, start_ns, end_ns]``. ``ProgramTrace`` is a
``tracefile.Trace`` that also keeps them. ``idle_by_program_span`` splits
each idle gap by overlap: each instant of a gap goes to the innermost
program span open on the host at that instant (the one that started
last), and instants in no program span go to ``OUTSIDE``. Unlike
``Trace.idle_by_activity`` it does not put a whole gap down to what the
host did at its midpoint.

A trace of a program that opens no such span gives an empty list, and
``per_step`` then gives None.
"""
from __future__ import annotations

import heapq
from typing import Dict, List, Optional, Tuple

from harness import tracefile

PREFIX = "repro."
OUTSIDE = "outside"
SAMPLING = ("repro.sample", "repro.fetch")
STEP = "repro.decode/step"
TICK = "repro.step"


def load(path) -> Dict:
    """``tracefile.load``'s records, plus the program's spans."""
    from jax.profiler import ProfileData

    rec = tracefile.load(path)
    program = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                program.extend([e.name, e.start_ns,
                                e.start_ns + e.duration_ns]
                               for e in line.events
                               if e.name.startswith(PREFIX))
    rec["program"] = program
    return rec


def innermost(spans) -> List[Tuple[float, float, str]]:
    """``(start, end, name)`` pieces of time, in order, over each of which
    one program span is the innermost open; time in none is left out."""
    edges = sorted({a for _, a, _ in spans} | {b for _, _, b in spans})
    order = sorted(spans, key=lambda s: s[1])
    open_: List[Tuple[float, float, str]] = []     # (-start, end, name)
    out: List[Tuple[float, float, str]] = []
    k = 0
    for t0, t1 in zip(edges, edges[1:]):
        while k < len(order) and order[k][1] <= t0:
            name, a, b = order[k]
            heapq.heappush(open_, (-a, b, name))
            k += 1
        while open_ and open_[0][1] <= t0:
            heapq.heappop(open_)
        if open_:
            name = open_[0][2]
            if out and out[-1][2] == name and out[-1][1] == t0:
                out[-1] = (out[-1][0], t1, name)
            else:
                out.append((t0, t1, name))
    return out


def overlap(gaps, pieces) -> Dict[str, float]:
    """Seconds of the sorted, disjoint ``gaps`` that each name's sorted,
    disjoint ``pieces`` cover; the rest under ``OUTSIDE``."""
    out: Dict[str, float] = {}
    j = 0
    for a, b in gaps:
        while j < len(pieces) and pieces[j][1] <= a:
            j += 1
        covered, m = 0.0, j
        while m < len(pieces) and pieces[m][0] < b:
            s0, s1, name = pieces[m]
            part = min(b, s1) - max(a, s0)
            out[name] = out.get(name, 0.0) + part * 1e-9
            covered += part
            m += 1
        if b - a > covered:
            out[OUTSIDE] = out.get(OUTSIDE, 0.0) + (b - a - covered) * 1e-9
    return out


class ProgramTrace(tracefile.Trace):
    """A reduced trace that also holds the program's spans that overlap
    the window, as ``(name, start_ns, end_ns)``."""

    def __init__(self, rec: Dict):
        super().__init__(rec)
        self.program = [(n, a, b) for n, a, b in rec.get("program", [])
                        if b > self.lo and a < self.hi]

    def idle_by_program_span(self) -> Dict[str, float]:
        """Idle seconds by the innermost program span open at each
        instant (see the module docstring)."""
        return overlap(self.gaps(), innermost(self.program))

    def started_in_window(self, name: str) -> int:
        """Program spans named ``name`` that start in the window."""
        return sum(1 for n, a, _ in self.program
                   if n == name and self.lo <= a < self.hi)

    def per_step(self) -> Optional[Dict[str, float]]:
        """Per decode step (a ``repro.decode/step`` span that starts in the
        window): the device's idle milliseconds inside sampling spans
        (``sample`` and ``fetch``), and inside ticks (``repro.step``) but
        outside sampling spans; and the token fetches, each a
        device-to-host read. None where no decode step started in the
        window."""
        steps = self.started_in_window(STEP)
        if not steps:
            return None
        idle = self.idle_by_program_span()
        sampling = sum(idle.get(n, 0.0) for n in SAMPLING)
        # sampling spans open only inside a tick
        ticks = [sp for sp in self.program if sp[0] == TICK]
        in_ticks = overlap(self.gaps(), innermost(ticks)).get(TICK, 0.0)
        scheduler = in_ticks - sampling
        return {
            "steps": steps,
            "sampling_idle_ms_per_step": sampling * 1e3 / steps,
            "scheduler_idle_ms_per_step": scheduler * 1e3 / steps,
            "host_syncs_per_step":
                self.started_in_window("repro.fetch") / steps,
        }


def reduce(rec: Dict) -> ProgramTrace:
    return ProgramTrace(rec)
