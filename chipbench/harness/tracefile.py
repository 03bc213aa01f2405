"""From a profiler trace to what the per-layer readers need.

``load`` reads the ``.xplane.pb`` file JAX's profiler writes (with nothing
but ``jax.profiler.ProfileData``) into plain records:

    {"host":    [[name, start_ns, end_ns], ...]   the harness's bench.* spans
     "modules": [[name, start_ns, end_ns], ...]   XLA programs on the device
     "ops":     [[name, start_ns, end_ns, chip], ...]  operations on chips
     "devices": number of chips traced}

Device and host events share the trace's clock. An operation that holds
others (the ``while`` of the scanned layers) overlaps them: busy time is a
union, and the list of the longest operations leaves such holders out.

``reduce`` turns those records into a ``Trace``: the traced window (the
host span ``bench.window``), the device's busy time in it, its idle gaps
and what the host was doing in each, and the time of programs and
operations by name. Device events are cut to the window. The reduction is
pure, so the tests drive it with a small recorded trace
(``tests/data/small_trace.json``).
"""
from __future__ import annotations

import re
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Tuple

# host span -> what the host was doing while the device sat idle
ACTIVITY = {
    "bench.prefill": "prefill dispatch",
    "bench.splice": "splice",
    "bench.decode": "decode dispatch",
    "bench.step": "sampling and scheduler",
    "bench.wait": "waiting for an arrival",
}
OUTSIDE = "harness between steps"
# operations that hold others: their time is their contents' time
HOLDERS = ("while", "conditional", "call")


def find(trace_dir) -> List[Path]:
    return list(Path(trace_dir).rglob("*.xplane.pb"))


def _op_name(name: str) -> str:
    """An operation's short name: the trace names each operation by its
    HLO text, ``%name.N = shape op(...)``; a Pallas kernel by the function
    that launches it (``%rm_fused_attention_pallas.6 = ...``)."""
    return re.sub(r"\.\d+$", "", name.split(" = ", 1)[0].lstrip("%"))


def load(path) -> Dict:
    """Read one ``.xplane.pb`` into plain records (see module docstring)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    host, modules, ops, devices = [], [], [], 0
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: line for line in plane.lines}
            if "XLA Ops" not in lines:
                continue            # not a chip (e.g. a Megascale plane)
            if "XLA Modules" in lines:
                modules.extend([e.name, e.start_ns,
                                e.start_ns + e.duration_ns]
                               for e in lines["XLA Modules"].events)
            ops.extend([_op_name(e.name), e.start_ns,
                        e.start_ns + e.duration_ns, devices]
                       for e in lines["XLA Ops"].events)
            devices += 1
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.name, e.start_ns, e.start_ns + e.duration_ns]
                            for e in line.events
                            if e.name.startswith("bench."))
    return {"host": host, "modules": modules, "ops": ops,
            "devices": max(devices, 1)}


def union(intervals: Iterable[Tuple[float, float]], lo: float,
          hi: float) -> List[Tuple[float, float]]:
    """Merged intervals clipped to [lo, hi]."""
    out: List[List[float]] = []
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


class Trace:
    def __init__(self, rec: Dict):
        win = [h for h in rec["host"] if h[0] == "bench.window"]
        if not win:
            raise ValueError("trace holds no bench.window span")
        self.lo, self.hi = win[0][1], win[0][2]
        self.devices = rec["devices"]
        self.ops = self._clip(rec["ops"])
        self.modules = self._clip(rec["modules"])
        per_chip: Dict[int, List[Tuple[float, float]]] = {}
        for op in rec["ops"]:
            per_chip.setdefault(op[3] if len(op) > 3 else 0, []).append(
                (op[1], op[2]))
        self.busy_per_chip = [union(v, self.lo, self.hi)
                              for v in per_chip.values()]
        self.host = [(n, a, b) for n, a, b in rec["host"]
                     if n != "bench.window" and b > self.lo and a < self.hi]
        self.busy = union(((a, b) for _, a, b in self.ops), self.lo,
                          self.hi)

    def _clip(self, events) -> List[Tuple[str, float, float]]:
        """Events that overlap the window, cut to it."""
        return [(e[0], max(e[1], self.lo), min(e[2], self.hi))
                for e in events if e[2] > self.lo and e[1] < self.hi]

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    @property
    def busy_s(self) -> float:
        """Seconds with an operation running, averaged over the chips."""
        return sum(b - a for busy in self.busy_per_chip
                   for a, b in busy) * 1e-9 / self.devices

    def gaps(self) -> List[Tuple[float, float]]:
        """Intervals of the window in which no chip runs an operation."""
        out, t = [], self.lo
        for a, b in self.busy:
            if a > t:
                out.append((t, a))
            t = b
        if t < self.hi:
            out.append((t, self.hi))
        return out

    def activity(self, t: float) -> str:
        """What the host was doing at ``t``: its innermost bench.* span."""
        best: Optional[Tuple[float, str]] = None
        for n, a, b in self.host:
            if a <= t < b and (best is None or b - a < best[0]):
                best = (b - a, n)
        return ACTIVITY.get(best[1], OUTSIDE) if best else OUTSIDE

    def idle_by_activity(self) -> Dict[str, float]:
        """Idle seconds by what the host was doing at each gap's midpoint."""
        out: Dict[str, float] = {}
        for a, b in self.gaps():
            act = self.activity((a + b) / 2)
            out[act] = out.get(act, 0.0) + (b - a) * 1e-9
        return out

    def module_time(self, part: str) -> Tuple[float, int]:
        """Device seconds and count of programs whose name holds ``part``."""
        sel = [(a, b) for n, a, b in self.modules if part in n]
        return sum(b - a for a, b in sel) * 1e-9, len(sel)

    def op_time(self, part: str) -> Tuple[float, int]:
        """Device seconds and count of operations whose name holds
        ``part``."""
        sel = [(a, b) for n, a, b in self.ops if part in n]
        return sum(b - a for a, b in sel) * 1e-9, len(sel)

    def top_ops(self, n: int = 10) -> List[List]:
        tot: Dict[str, float] = {}
        for name, a, b in self.ops:
            if name in HOLDERS:
                continue
            tot[name] = tot.get(name, 0.0) + (b - a) * 1e-9
        return [[k, v] for k, v in sorted(tot.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def breakdown(self) -> Dict[str, List[List]]:
        idle = sorted(self.idle_by_activity().items(),
                      key=lambda kv: -kv[1])[:10]
        return {"device_ops": self.top_ops(10),
                "idle_gaps": [[k, v] for k, v in idle]}


def reduce(rec: Dict) -> Trace:
    return Trace(rec)
