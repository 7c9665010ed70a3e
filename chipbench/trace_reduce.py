"""From a profiler trace (``.xplane.pb``) to the numbers the readers use.

Reads the file with ``jax.profiler.ProfileData`` alone. What it takes from
a trace:

* the device planes (``/device:TPU:<i>``): on each, the line of single
  operations (``XLA Ops``) gives the busy intervals, and the line of whole
  programs (``XLA Modules``) the seconds per program, the variants that
  the compiler tells apart by a fingerprint (``jit_name(1234)``) together;
* from the host planes, the annotations the benchmark's own client wrote:
  ``q:<shape>`` around each request, ``chipbench:slice`` around the traced
  slice. They sit on the same clock as the device's events.

Busy time is the length of the union of the operation intervals, so nested
and overlapping operations count once. Times are seconds; the trace's own
nanoseconds are kept only inside this file.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

Interval = Tuple[float, float]

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
REQUEST_PREFIX = "q:"
SLICE_NAME = "chipbench:slice"
NOTHING_IN_FLIGHT = "no request in flight"
FINGERPRINT = re.compile(r"\(\d+\)$")  # jit_name(1234): one program, many shapes


def union(intervals: List[Interval]) -> List[Interval]:
    """Sorted, disjoint intervals that cover the same points."""
    out: List[Interval] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


def covered(merged: List[Interval], lo: float, hi: float) -> float:
    """Length of ``merged`` (disjoint, sorted) inside [lo, hi]."""
    return sum(
        min(b, hi) - max(a, lo) for a, b in merged if b > lo and a < hi
    )


def gaps(merged: List[Interval], lo: float, hi: float) -> List[Interval]:
    """The parts of [lo, hi] that ``merged`` leaves uncovered."""
    out, at = [], lo
    for a, b in merged:
        if b <= lo:
            continue
        if a >= hi:
            break
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if at < hi:
        out.append((at, hi))
    return out


@dataclass
class Trace:
    """One traced slice, reduced."""

    slice: Interval
    busy: List[List[Interval]]  # per device, disjoint and sorted
    modules: Dict[str, float]  # seconds per program, summed over devices
    requests: List[Tuple[str, float, float]] = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return self.slice[1] - self.slice[0]

    def busy_in(self, lo: float, hi: float) -> float:
        """Busy seconds inside [lo, hi], averaged over the devices."""
        return sum(covered(b, lo, hi) for b in self.busy) / len(self.busy)

    @property
    def busy_s(self) -> float:
        return self.busy_in(*self.slice)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def requests_in_slice(self, shape: Optional[str] = None):
        """Requests that began and ended inside the slice."""
        lo, hi = self.slice
        return [
            r for r in self.requests
            if r[1] >= lo and r[2] <= hi and (shape is None or r[0] == shape)
        ]

    def busy_in_shape(self, shape: str) -> Optional[float]:
        """Busy seconds inside one shape's requests in the slice (the mean,
        where the slice holds several); None where it holds none."""
        found = self.requests_in_slice(shape)
        if not found:
            return None
        return sum(self.busy_in(lo, hi) for _, lo, hi in found) / len(found)

    def idle_by_request(self) -> Dict[str, float]:
        """Idle seconds of the first device inside the slice, each gap put
        to the shape of the request that covers most of it."""
        out: Dict[str, float] = {}
        for a, b in gaps(self.busy[0], *self.slice):
            best, most = NOTHING_IN_FLIGHT, 0.0
            for shape, lo, hi in self.requests:
                over = min(b, hi) - max(a, lo)
                if over > most:
                    best, most = REQUEST_PREFIX + shape, over
            out[best] = out.get(best, 0.0) + (b - a)
        return out

    def breakdown(self, top: int = 10) -> dict:
        def first(d):
            return [
                [k, v] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]
            ]

        return {
            "device_ops": first(self.modules),
            "idle_gaps": first(self.idle_by_request()),
        }


def reduce_trace(path: str) -> Optional[Trace]:
    """The trace at ``path``, reduced; None where it holds no device plane
    with operations on it (a CPU run's trace) or no slice annotation."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    busy, modules, requests, slices = [], {}, [], []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            ops: List[Interval] = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = [
                        (e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                        for e in line.events
                    ]
                elif line.name == MODULES_LINE:
                    for e in line.events:
                        name = FINGERPRINT.sub("", e.name)
                        modules[name] = (
                            modules.get(name, 0.0) + e.duration_ns * 1e-9
                        )
            if ops:
                busy.append(union(ops))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    name = e.name
                    if name.startswith(REQUEST_PREFIX) or name == SLICE_NAME:
                        iv = (e.start_ns * 1e-9,
                              (e.start_ns + e.duration_ns) * 1e-9)
                        if name == SLICE_NAME:
                            slices.append(iv)
                        else:
                            requests.append(
                                (name[len(REQUEST_PREFIX):], *iv)
                            )
    if not busy or not slices:
        return None
    return Trace(slices[0], busy, modules, sorted(requests, key=lambda r: r[1]))
