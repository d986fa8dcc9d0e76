"""Reduce a profiler trace of the window to device metrics.

    python bench/trace.py <file.xplane.pb>   # what a trace holds, for a reader

The window is the host span ``bench.window`` that the harness opens at the
first window step and closes after the last.  Inside it, for each TPU core
plane (``/device:TPU:<n>``), the events of the line ``XLA Ops`` are the
device's operations, named by their HLO instruction (``fusion.498``,
``ssd_scan.15``).  They nest (a ``while`` holds its body's operations), so
busy time is the union of their intervals and an operation's time is its
self time, less the operations inside it.  An idle gap is named by what
the host did for most of it: a harness span (``bench.next_batch``: the
loader; ``bench.decode``: decoding a payload), or ``trainer`` for the rest
(stacking, the copy to the device, dispatch, reading the loss back).
"""
from __future__ import annotations

import dataclasses
import glob
import os
import shutil
import sys
import tempfile
from typing import Callable, Dict, List, Tuple

WINDOW = "bench.window"
HOST_SPANS = ("bench.next_batch", "bench.decode")
OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float  # mean over the chips that ran anything
    ops: Dict[str, Tuple[int, float]]  # op name -> (events, self seconds), all chips
    gaps: List[Tuple[str, float]]  # (host span, seconds), longest first

    def op_time(self, match: Callable[[str], bool]) -> Tuple[int, float]:
        """Events and device seconds of the ops whose name ``match`` accepts."""
        n, s = 0, 0.0
        for name, (count, seconds) in self.ops.items():
            if match(name):
                n += count
                s += seconds
        return n, s

    def breakdown(self, top: int = 10) -> Dict[str, List]:
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1][1])[:top]
        return {
            "device_ops": [[name, seconds] for name, (_, seconds) in ops],
            "idle_gaps": [[name, seconds] for name, seconds in self.gaps[:top]],
        }


def _merge(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def _self_times(events: List[Tuple[str, int, int]]):
    """(name, events, self ns) per name: each event's time less that of the
    events nested directly inside it."""
    out: Dict[str, List[int]] = {}
    stack: List[List] = []  # [name, end, self]
    for name, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= a:
            done = stack.pop()
            out.setdefault(done[0], []).append(done[2])
        if stack:
            stack[-1][2] -= b - a
        stack.append([name, b, b - a])
    for done in stack:
        out.setdefault(done[0], []).append(done[2])
    return [(name, len(v), sum(v)) for name, v in out.items()]


def _overlap(a: int, b: int, spans: List[Tuple[int, int]]) -> int:
    return sum(max(0, min(b, y) - max(a, x)) for x, y in spans)


def summarize(data) -> Summary:
    """``data``: a ``jax.profiler.ProfileData`` of a traced window."""
    host: Dict[str, List[Tuple[int, int]]] = {name: [] for name in (WINDOW, *HOST_SPANS)}
    device: Dict[str, List] = {}
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device.setdefault(plane.name, []).extend(
                        (e.name.split(" = ", 1)[0].lstrip("%"), e.start_ns, e.end_ns)
                        for e in line.events
                    )
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in host:
                        host[e.name].append((e.start_ns, e.end_ns))
    if len(host[WINDOW]) != 1:
        raise ValueError(f"expected one {WINDOW!r} span, found {len(host[WINDOW])}")
    lo, hi = host[WINDOW][0]
    ops: Dict[str, Tuple[int, float]] = {}
    busy, gaps = [], []
    for events in device.values():
        inside = [(n, max(a, lo), min(b, hi)) for n, a, b in events if b > lo and a < hi]
        if not inside:
            continue
        for name, count, self_ns in _self_times(inside):
            n, seconds = ops.get(name, (0, 0.0))
            ops[name] = (n + count, seconds + self_ns * 1e-9)
        merged = _merge([(a, b) for _, a, b in inside])
        busy.append(sum(b - a for a, b in merged))
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                parts = {span: _overlap(a, b, host[span]) for span in HOST_SPANS}
                parts["trainer"] = (b - a) - sum(parts.values())
                gaps.append((max(parts, key=parts.get), (b - a) * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    mean_busy = sum(busy) / len(busy) * 1e-9 if busy else 0.0
    return Summary((hi - lo) * 1e-9, mean_busy, ops, gaps)


class Tracer:
    """``jax.profiler`` around the window, into a directory of its own, with
    the ``bench.window`` span marking the window inside the trace."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self._window = None

    def start(self) -> None:
        import jax

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # no Python function events: they slow the host
        options.enable_hlo_proto = False
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self._window = jax.profiler.TraceAnnotation(WINDOW)
        self._window.__enter__()

    def stop(self) -> None:
        import jax

        if self._window is not None:
            self._window.__exit__(None, None, None)
            jax.profiler.stop_trace()

    def summary(self) -> Summary:
        from jax.profiler import ProfileData

        try:
            (path,) = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"), recursive=True)
            return summarize(ProfileData.from_file(path))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def describe(path: str) -> None:
    """Planes, lines, event counts and the commonest event names."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    for plane in data.planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            names: Dict[str, int] = {}
            for e in events:
                names[e.name] = names.get(e.name, 0) + 1
            common = sorted(names.items(), key=lambda kv: -kv[1])[:12]
            first = (events[0].start_ns, events[-1].end_ns) if events else None
            print(f"  line {line.name!r}: {len(events)} events, span {first}")
            for name, n in common:
                print(f"    {n:6d}  {name[:160]}")


if __name__ == "__main__":
    describe(sys.argv[1])
