"""Reduce a JAX profiler trace to the numbers the benchmark reports.

Two stages, so that the second can be checked on a small recorded trace:

* :func:`load_events` reads an ``.xplane.pb`` with nothing but JAX: from
  each device plane (``/device:TPU:<i>``) the program executions (line
  ``XLA Modules``) and the operations (line ``XLA Ops``); from the host
  plane every event on the threads that carry the benchmark's own spans
  (names starting ``bench.``), so that a gap can be put down to what that
  thread was doing.
* :class:`Reduction` computes device busy time as the union of operation
  intervals, the idle share over the traced window, device time per
  program (matched by substrings of the program's name), and the
  ``breakdown`` of the result line.

Times are kept in integer nanoseconds and reported in seconds.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:"
NAME_CHARS = 120           # an op's name is its whole HLO line: keep the head


@dataclasses.dataclass(frozen=True)
class Ev:
    name: str
    start: int      # ns
    end: int        # ns


def _events(line) -> List[Ev]:
    return [Ev(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
            for e in line.events]


def load_events(path: str) -> Dict:
    """``{"devices": {plane: {"modules": [...], "ops": [...]}},
    "host": [...]}`` from one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[str, Dict[str, List[Ev]]] = {}
    host: List[Ev] = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = {line.name: line for line in plane.lines}
            if OPS_LINE not in lines:
                continue
            devices[plane.name] = {
                "modules": (_events(lines[MODULES_LINE])
                            if MODULES_LINE in lines else []),
                "ops": _events(lines[OPS_LINE])}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = _events(line)
                if any(e.name.startswith(SPAN_PREFIX) for e in evs):
                    host.extend(evs)
    return {"devices": devices, "host": host}


def to_json(events: Dict) -> Dict:
    """A JSON-able copy of :func:`load_events`' result (for recording)."""
    def conv(evs):
        return [[e.name, e.start, e.end] for e in evs]
    return {"devices": {p: {k: conv(v) for k, v in d.items()}
                        for p, d in events["devices"].items()},
            "host": conv(events["host"])}


def from_json(obj: Dict) -> Dict:
    def conv(rows):
        return [Ev(str(n), int(s), int(e)) for n, s, e in rows]
    return {"devices": {p: {k: conv(v) for k, v in d.items()}
                        for p, d in obj["devices"].items()},
            "host": conv(obj["host"])}


def union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merged, sorted, disjoint intervals."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(intervals, lo: int, hi: int):
    for s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            yield s, e


def _short(name: str) -> str:
    """Program name without the trailing ``(<id>)`` the runtime adds."""
    i = name.rfind("(")
    return name[:i] if i > 0 and name.endswith(")") else name


class Reduction:
    """Reductions over one traced window."""

    def __init__(self, events: Dict):
        self.devices = events["devices"]
        self.host = sorted(events["host"], key=lambda e: (e.start, -e.end))
        win = [e for e in self.host if e.name == WINDOW_SPAN]
        if win:
            self.lo, self.hi = win[0].start, win[0].end
        else:
            ends = [e for d in self.devices.values() for e in d["ops"]]
            ends += self.host
            self.lo = min((e.start for e in ends), default=0)
            self.hi = max((e.end for e in ends), default=0)
        self._busy = {p: union(_clip(((e.start, e.end) for e in d["ops"]),
                                     self.lo, self.hi))
                      for p, d in self.devices.items()}

    # -- the device record ------------------------------------------------

    @property
    def n_devices(self) -> int:
        return max(len(self.devices), 1)

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        return sum(e - s for b in self._busy.values() for s, e in b) \
            * 1e-9 / self.n_devices

    @property
    def idle_share(self) -> Optional[float]:
        if self.hi <= self.lo or not self.devices:
            return None
        return 1.0 - self.busy_s / self.window_s

    # -- programs and spans -----------------------------------------------

    def program_time(self, substrings: Sequence[str]) -> float:
        """Device seconds, averaged over the chips, of the programs whose
        name holds any of ``substrings``, inside the window."""
        total = 0
        for d in self.devices.values():
            hits = [(e.start, e.end) for e in d["modules"]
                    if any(s in e.name for s in substrings)]
            total += sum(e - s for s, e in _clip(hits, self.lo, self.hi))
        return total * 1e-9 / self.n_devices

    def spans(self, name: str) -> List[Ev]:
        """Host spans called ``name`` that start inside the window."""
        return [e for e in self.host
                if e.name == name and self.lo <= e.start < self.hi]

    def busy_within(self, lo: int, hi: int) -> float:
        """Device busy seconds inside ``[lo, hi)``, averaged over chips."""
        total = 0
        for b in self._busy.values():
            i = bisect.bisect_left(b, (lo, lo)) - 1
            for s, e in b[max(i, 0):]:
                if s >= hi:
                    break
                total += max(0, min(e, hi) - max(s, lo))
        return total * 1e-9 / self.n_devices

    def idle_gaps(self) -> List[Tuple[int, int]]:
        """Gaps between device operations on the first chip, in the
        window."""
        if not self._busy:
            return [(self.lo, self.hi)]
        b = self._busy[sorted(self._busy)[0]]
        gaps, cur = [], self.lo
        for s, e in b:
            if s > cur:
                gaps.append((cur, s))
            cur = max(cur, e)
        if self.hi > cur:
            gaps.append((cur, self.hi))
        return gaps

    def host_activity(self, t: int) -> str:
        """The innermost host event running at time ``t`` on the
        benchmark's threads, or ``"no host span"``."""
        best = None
        for e in self.host:
            if e.start > t:
                break
            if e.end > t and (best is None or e.end - e.start
                              < best.end - best.start):
                best = e
        return best.name if best is not None else "no host span"

    def breakdown(self, top: int = 10) -> Dict[str, List]:
        """The device operations that took most time (``program:op``,
        seconds summed over the window, first chip) and the longest idle
        gaps, each named by what the host was doing in its middle."""
        ops: Dict[str, int] = defaultdict(int)
        if self.devices:
            d = self.devices[sorted(self.devices)[0]]
            mods = sorted(d["modules"], key=lambda e: e.start)
            starts = [m.start for m in mods]
            for e in d["ops"]:
                if not (self.lo <= e.start < self.hi):
                    continue
                i = bisect.bisect_right(starts, e.start) - 1
                mod = (_short(mods[i].name)
                       if i >= 0 and mods[i].end > e.start else "?")
                ops[f"{mod}:{e.name}"[:NAME_CHARS]] += e.end - e.start
        top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_gaps(), key=lambda g: g[0] - g[1])[:top]
        return {"device_ops": [[n, v * 1e-9] for n, v in top_ops],
                "idle_gaps": [[self.host_activity((s + e) // 2),
                               (e - s) * 1e-9] for s, e in gaps]}


def find_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def reduce_dir(trace_dir: str) -> Reduction:
    return Reduction(load_events(find_xplane(trace_dir)))
