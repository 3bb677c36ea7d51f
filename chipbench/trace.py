"""Profiler trace of a window, reduced to device busy time, module time
and idle gaps.

The JAX profiler writes an XSpace (``*.xplane.pb``).  On a TPU each chip is
a plane ``/device:TPU:<n>`` whose line ``XLA Ops`` holds one event per
operation run and whose line ``XLA Modules`` holds one event per program
run, named ``jit_<function>(<fingerprint>)``.  Host planes hold the
``TraceAnnotation`` spans the harness opens (``chipbench.*``).  All events
share one clock, in nanoseconds from the start of the trace.

Busy time is the union of operation intervals inside the window, averaged
over the chips used; an idle gap is a stretch of the window in which no
operation ran, labelled with the innermost harness span that covers its
middle on the host.
"""

from __future__ import annotations

import glob
import os
import re
import shutil
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

#: Host span that marks the traced window.
WINDOW_SPAN = "chipbench.window"
#: Prefix of the host spans the harness opens around calls into the program.
SPAN_PREFIX = "chipbench."
#: Label of idle time that no harness span covers.
NO_SPAN = "no harness span"

_FINGERPRINT = re.compile(r"\(\d+\)$")

Interval = Tuple[float, float]              # start, end (ns)


def module_name(event_name: str) -> str:
    """``jit__frontier_fused_impl(1610...)`` -> ``jit__frontier_fused_impl``."""
    return _FINGERPRINT.sub("", event_name)


@dataclass
class DeviceEvents:
    """One chip's events: operation intervals and named module runs."""
    ops: List[Interval] = field(default_factory=list)
    modules: List[Tuple[str, float, float]] = field(default_factory=list)


@dataclass
class Summary:
    window_s: float
    busy_s: float                              # mean over chips
    module_s: Dict[str, float]                 # summed over chips
    idle_gaps: List[Tuple[str, float]]         # (host label, seconds)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


# ------------------------------------------------------------ recording --
def start(directory: str) -> None:
    import jax

    shutil.rmtree(directory, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1            # the annotations, not every TraceMe
    opts.python_tracer_level = 0
    jax.profiler.start_trace(directory, profiler_options=opts)


def stop() -> None:
    import jax

    jax.profiler.stop_trace()


def read_dir(directory: str):
    """Events of the one trace under ``directory``; the directory is
    removed once read."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {directory}, "
                           f"found {len(paths)}")
    try:
        return events(ProfileData.from_file(paths[0]))
    finally:
        shutil.rmtree(directory, ignore_errors=True)


def events(profile) -> Tuple[Dict[str, DeviceEvents],
                             List[Tuple[str, float, float]]]:
    """(per TPU plane its events, host spans of the harness) of a
    ``jax.profiler.ProfileData``."""
    devices: Dict[str, DeviceEvents] = {}
    spans: List[Tuple[str, float, float]] = []
    for plane in profile.planes:
        if plane.name.startswith("/device:TPU:"):
            d = devices.setdefault(plane.name, DeviceEvents())
            for line in plane.lines:
                if line.name == "XLA Ops":
                    d.ops += [(e.start_ns, e.start_ns + e.duration_ns)
                              for e in line.events]
                elif line.name == "XLA Modules":
                    d.modules += [(module_name(e.name), e.start_ns,
                                   e.start_ns + e.duration_ns)
                                  for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                          for e in line.events
                          if e.name.startswith(SPAN_PREFIX)]
    return devices, spans


# ------------------------------------------------------------ reduction --
def union(intervals: Iterable[Interval], lo: float, hi: float
          ) -> List[Interval]:
    """Sorted disjoint intervals covering ``intervals`` clipped to
    ``[lo, hi]``."""
    out: List[List[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _labels(times: Sequence[float],
            spans: Sequence[Tuple[str, float, float]]) -> List[str]:
    """For each time, the name of the shortest harness span covering it
    (a sweep over span starts and ends; the active set stays small)."""
    marks = []
    for i, (name, s, e) in enumerate(spans):
        if name != WINDOW_SPAN:
            marks += [(s, 0, i), (e, 2, i)]
    marks += [(t, 1, j) for j, t in enumerate(times)]
    active: Dict[int, float] = {}
    out = [NO_SPAN] * len(times)
    for _, kind, i in sorted(marks):
        if kind == 0:
            active[i] = spans[i][2] - spans[i][1]
        elif kind == 2:
            active.pop(i, None)
        elif active:
            out[i] = spans[min(active, key=active.get)][0]
    return out


def summarize(devices: Dict[str, DeviceEvents],
              spans: Sequence[Tuple[str, float, float]],
              chips: int, window: Optional[Interval] = None) -> Summary:
    """Reduce one trace.  ``window`` defaults to the host span
    ``chipbench.window``; ``chips`` is how many TPU planes the run used
    (the busy time is their mean)."""
    if window is None:
        marks = [(s, e) for name, s, e in spans if name == WINDOW_SPAN]
        if len(marks) != 1:
            raise ValueError(f"expected one {WINDOW_SPAN} span, "
                             f"found {len(marks)}")
        window = marks[0]
    lo, hi = window
    planes = sorted(devices)[:chips]
    busy_ns = 0.0
    module_ns: Dict[str, float] = defaultdict(float)
    gaps: Dict[str, float] = defaultdict(float)
    for name in planes:
        d = devices[name]
        covered = union(d.ops, lo, hi)
        busy_ns += sum(e - s for s, e in covered)
        for mod, s, e in d.modules:
            s, e = max(s, lo), min(e, hi)
            if e > s:
                module_ns[mod] += e - s
        edges = [lo] + [x for iv in covered for x in iv] + [hi]
        idle = [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
        for (s, e), label in zip(idle, _labels([(s + e) / 2
                                                for s, e in idle], spans)):
            gaps[label] += e - s
    n = max(len(planes), 1)
    return Summary(
        window_s=(hi - lo) * 1e-9, busy_s=busy_ns / n * 1e-9,
        module_s={k: v * 1e-9 for k, v in module_ns.items()},
        idle_gaps=sorted(((k, v / n * 1e-9) for k, v in gaps.items()),
                         key=lambda kv: -kv[1]))


def breakdown(summary: Summary, top: int = 10) -> dict:
    """The result line's ``breakdown``: the programs that took most device
    time, and idle time by what the host was doing."""
    mods = sorted(summary.module_s.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in mods],
            "idle_gaps": [[k, v] for k, v in summary.idle_gaps[:top]]}
