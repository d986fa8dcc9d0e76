"""The device's idle time in the window, put down to the trainer's host phases.

    python -m bench.phases <file.xplane.pb>   # idle under each phase, longest gaps

``Trainer.train`` (``training/loop.py``) opens a profiler span for each host
phase of a step: ``train.next_batch`` (the request to the loader),
``train.prepare`` (decode, stack, the copy to the device), ``train.dispatch``
(the call of the jitted step), ``train.block`` (waiting for its result) and
``train.read_loss``.  They lie on the clock of the device's ``XLA Ops``, so
each idle gap of a chip inside the window ``bench.window`` splits by which
phase span was open on the host; idle time under none goes to ``""``.
``trace.summarize`` names a gap by the harness's spans (``bench.next_batch``,
``bench.decode``) or else ``trainer``; here such a ``trainer`` gap takes the
name of the phase span over most of it, and stays ``trainer`` only where no
phase span is open.  A trace without the program's spans splits all its idle
time to ``""`` and names its gaps as ``trace.summarize`` does.
"""
from __future__ import annotations

import dataclasses
import sys
from typing import Dict, List, Tuple

from bench import trace

PHASES = (
    "train.next_batch",
    "train.prepare",
    "train.dispatch",
    "train.block",
    "train.read_loss",
)


@dataclasses.dataclass
class PhaseSplit:
    # Idle seconds of the window under each phase span seen in it, and "" for
    # those under none; mean over the chips that ran anything, as
    # ``Summary.busy_s`` is, so the values sum to ``window_s - busy_s``.
    idle_by_span: Dict[str, float]
    gaps: List[Tuple[str, float]]  # (name, seconds), longest first

    def idle_outside(self, phase: str) -> float:
        """Idle seconds under any other phase span or under none."""
        return sum(s for name, s in self.idle_by_span.items() if name != phase)


def split(data) -> PhaseSplit:
    """``data``: a ``jax.profiler.ProfileData`` of a traced window."""
    names = (trace.WINDOW, *trace.HOST_SPANS, *PHASES)
    host: Dict[str, List[Tuple[int, int]]] = {name: [] for name in names}
    device: Dict[str, List[Tuple[int, int]]] = {}
    for plane in data.planes:
        if plane.name.startswith(trace.DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == trace.OPS_LINE:
                    device.setdefault(plane.name, []).extend(
                        (e.start_ns, e.end_ns) for e in line.events
                    )
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in host:
                        host[e.name].append((e.start_ns, e.end_ns))
    if len(host[trace.WINDOW]) != 1:
        raise ValueError(f"expected one {trace.WINDOW!r} span, found {len(host[trace.WINDOW])}")
    lo, hi = host[trace.WINDOW][0]
    idle = {"": 0}
    for phase in PHASES:
        if any(b > lo and a < hi for a, b in host[phase]):
            idle[phase] = 0
    chips, gaps = 0, []
    for events in device.values():
        inside = [(max(a, lo), min(b, hi)) for a, b in events if b > lo and a < hi]
        if not inside:
            continue
        chips += 1
        edges = [lo] + [x for ab in trace._merge(inside) for x in ab] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            # One thread opens the phase spans one after another, so they
            # never overlap and their shares of a gap add up to at most it.
            under = {p: trace._overlap(a, b, host[p]) for p in PHASES}
            for phase, ns in under.items():
                if ns:
                    idle[phase] += ns
            idle[""] += (b - a) - sum(under.values())
            parts = {span: trace._overlap(a, b, host[span]) for span in trace.HOST_SPANS}
            parts["trainer"] = (b - a) - sum(parts.values())
            name = max(parts, key=parts.get)
            if name == "trainer" and any(under.values()):
                name = max(under, key=under.get)
            gaps.append((name, (b - a) * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    return PhaseSplit({k: v * 1e-9 / chips for k, v in idle.items()} if chips else {}, gaps)


def main(path: str) -> None:
    from jax.profiler import ProfileData

    s = split(ProfileData.from_file(path))
    for name, seconds in sorted(s.idle_by_span.items(), key=lambda kv: -kv[1]):
        print(f"idle {seconds!r} s under {name or '(no phase span)'}")
    for name, seconds in s.gaps[:10]:
        print(f"gap {seconds!r} s {name}")


if __name__ == "__main__":
    main(sys.argv[1])
