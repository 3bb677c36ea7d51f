"""Span-based tracing of a query's lifecycle, plus the bounded event logs.

A :class:`Span` is one named interval with attributes and children; the
serving layer builds one tree per query — submit → (queue_wait ⊃
engine_wait) → flush → chunk → pack_build → execute (⊃ plan:*, readback,
compile) — carried on
``Query.trace`` / ``FlushEvent.span`` and appended to the owning server's
bounded ``trace_log``.  Trees may *share* subtrees: a flush that answers
five queries is one flush span appearing under five query roots, which is
exactly the batching the engine performed.

Propagation is ambient: :func:`span` (and :func:`activate`) push the
current span **and its clock** onto a :class:`contextvars.ContextVar`, so
instrumented library code (``plan_stage`` in core/batch.py,
search/engine.py) attaches children to whatever query is executing without
any parameter threading — and reads time from the same injectable clock
domain as the server that opened the root (simulated-clock tests stay
deterministic).  Context vars are per-thread, so concurrent flushes build
disjoint trees.

When no span is active, ``plan_stage`` still feeds the global
``repro_plan_build_seconds`` histogram and costs one contextvar read
otherwise — instrumentation must be safe to leave on everywhere.

Profiler clock: every span opened and closed on one thread (:func:`span`,
:func:`activate`, :func:`plan_stage`, :func:`readback`) also emits a
``jax.profiler.TraceAnnotation`` named ``repro.<span name>``, so a
captured profile holds the program's stages on the same clock as the
device's ``XLA Ops``.  Outside a profiler session an annotation costs one
TraceMe activity check; there is no switch.  Spans built after the fact
(the query root, ``queue_wait``, which cross threads) stay host-tree-only.

Device→host reads go through :func:`readback`, and JAX's own backend
compile events (``/jax/core/compile/backend_compile_duration``, a compile
or a load from the persistent cache) reach a process-wide listener that
appends a ``compile`` child to the ambient span and reports the duration
to the sink :func:`compiles_to` installed (the server executing the
chunk) — so an execution's host self time is ``execute − readback −
compile``.
"""

from __future__ import annotations

import functools
import math
import time
from collections import deque
from contextlib import contextmanager
from contextvars import ContextVar
from dataclasses import dataclass, field
from typing import Callable, Dict, Iterator, List, Optional, Tuple

import jax

from .registry import global_registry

__all__ = ["Span", "span", "activate", "current", "current_clock",
           "plan_stage", "readback", "readback_seconds", "annotation",
           "compiles_to", "device_scope", "BoundedLog", "span_problems"]

#: Prefix of the program's profiler annotations and device name scopes.
PREFIX = "repro."
#: JAX's duration event for one backend compile (or persistent-cache load).
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@dataclass
class Span:
    """One named interval in a query's lifecycle tree."""
    name: str
    t0: float
    t1: float = math.nan               # nan until finish()
    attrs: Dict[str, object] = field(default_factory=dict)
    children: List["Span"] = field(default_factory=list)

    def finish(self, t: float) -> "Span":
        if not self.finished:
            self.t1 = t
        return self

    @property
    def finished(self) -> bool:
        return not math.isnan(self.t1)

    @property
    def duration(self) -> float:
        return (self.t1 - self.t0) if self.finished else math.nan

    def walk(self) -> Iterator["Span"]:
        """Pre-order traversal of the tree rooted here."""
        stack = [self]
        while stack:
            s = stack.pop()
            yield s
            stack.extend(reversed(s.children))

    def find(self, name: str) -> List["Span"]:
        return [s for s in self.walk() if s.name == name]

    def to_dict(self) -> dict:
        """JSON-safe rendering (shared subtrees are duplicated)."""
        return {"name": self.name, "t0": self.t0,
                "t1": None if not self.finished else self.t1,
                "attrs": dict(self.attrs),
                "children": [c.to_dict() for c in self.children]}


# (active span, its clock) — per-thread/task via contextvars
_ACTIVE: ContextVar[Optional[Tuple[Span, Callable[[], float]]]] = \
    ContextVar("repro_obs_active_span", default=None)


# where the compile listener reports durations: the server executing the
# ambient chunk (:func:`compiles_to`)
_COMPILE_SINK: ContextVar[Optional[Callable[[float], None]]] = \
    ContextVar("repro_obs_compile_sink", default=None)


def annotation(name: str) -> jax.profiler.TraceAnnotation:
    """A profiler annotation ``repro.<name>`` on this thread: an event in
    a captured profile's host plane, a TraceMe activity check otherwise."""
    return jax.profiler.TraceAnnotation(PREFIX + name)


def device_scope(name: str) -> Callable:
    """Decorator: trace the function inside ``jax.named_scope("repro.<name>")``
    so its operations carry a stable name in the compiled program's
    metadata (a fresh scope per call; the traced code does not change)."""
    def deco(fn: Callable) -> Callable:
        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            with jax.named_scope(PREFIX + name):
                return fn(*args, **kwargs)
        return scoped
    return deco


def current() -> Optional[Span]:
    """The ambient span, or None outside any instrumented scope."""
    top = _ACTIVE.get()
    return None if top is None else top[0]


def current_clock() -> Callable[[], float]:
    """The clock of the ambient span (``time.monotonic`` outside one)."""
    top = _ACTIVE.get()
    return time.monotonic if top is None else top[1]


@contextmanager
def activate(s: Span, clock: Callable[[], float]):
    """Make an *externally managed* span ambient: children attach to it,
    but entering/exiting does not start/finish it (the serving layer opens
    query roots at submit time and finishes them when futures resolve)."""
    token = _ACTIVE.set((s, clock))
    try:
        with annotation(s.name):
            yield s
    finally:
        _ACTIVE.reset(token)


@contextmanager
def span(name: str, clock: Optional[Callable[[], float]] = None,
         attrs: Optional[dict] = None):
    """Open a child of the ambient span (or a root), finish it on exit.
    Without an explicit ``clock`` the parent's clock domain is inherited."""
    parent = _ACTIVE.get()
    clk = clock if clock is not None else (
        parent[1] if parent is not None else time.monotonic)
    s = Span(name, clk(), attrs=dict(attrs) if attrs else {})
    if parent is not None:
        parent[0].children.append(s)
    token = _ACTIVE.set((s, clk))
    try:
        with annotation(name):
            yield s
    finally:
        _ACTIVE.reset(token)
        s.finish(clk())


@contextmanager
def readback():
    """One device→host read (``np.asarray`` / ``int()`` of a device
    array): a ``readback`` child of the ambient span, or only the
    annotation outside any span.  It adds no synchronization: the read it
    wraps is the host's wait on the device."""
    if _ACTIVE.get() is None:
        with annotation("readback"):
            yield None
    else:
        with span("readback") as s:
            yield s


def readback_seconds(root: Span) -> float:
    """Summed duration of the outermost ``readback`` spans under ``root``."""
    total, stack = 0.0, [root]
    while stack:
        s = stack.pop()
        if s.name == "readback" and s is not root:
            total += s.duration
        else:
            stack.extend(s.children)
    return total


@contextmanager
def compiles_to(sink: Callable[[float], None]):
    """Report every backend compile on this thread inside the block to
    ``sink(seconds)`` (the executing server's counter and stage)."""
    token = _COMPILE_SINK.set(sink)
    try:
        yield
    finally:
        _COMPILE_SINK.reset(token)


def _on_duration_event(event: str, duration: float, **kwargs) -> None:
    """JAX monitoring listener; runs on the compiling thread as the
    compile ends.  The ``compile`` child starts no earlier than its parent
    or an earlier sibling: on a simulated clock the compile's real
    duration would reach back past them."""
    if event != COMPILE_EVENT:
        return
    top = _ACTIVE.get()
    if top is not None:
        parent, clk = top
        now = clk()
        t0 = max(now - duration, parent.t0,
                 parent.children[-1].t0 if parent.children else parent.t0)
        parent.children.append(Span(
            "compile", t0, attrs={"fun_name": kwargs.get("fun_name")}
        ).finish(now))
    sink = _COMPILE_SINK.get()
    if sink is not None:
        sink(duration)


jax.monitoring.register_event_duration_secs_listener(_on_duration_event)


@contextmanager
def plan_stage(plan: str):
    """Instrument one host-side plan construction (the lazy pack memos:
    ``ell`` / ``sequence`` / ``search_stats``).  Attaches a ``plan:<name>``
    child to the ambient span when one is active, and always feeds the
    global ``repro_plan_build_seconds{plan=...}`` histogram — plan builds
    happen inside cached properties, so which *query* paid the build cost
    is visible only through this hook."""
    parent = _ACTIVE.get()
    clk = parent[1] if parent is not None else time.monotonic
    t0 = clk()
    s: Optional[Span] = None
    if parent is not None:
        s = Span(f"plan:{plan}", t0)
        parent[0].children.append(s)
    token = _ACTIVE.set((s, clk)) if s is not None else None
    try:
        with annotation(f"plan:{plan}"):
            yield s
    finally:
        if token is not None:
            _ACTIVE.reset(token)
        t1 = clk()
        if s is not None:
            s.finish(t1)
        global_registry().histogram(
            "repro_plan_build_seconds",
            "host-side plan construction per lazy pack memo",
            ("plan",)).labels(plan).observe(t1 - t0)


class BoundedLog:
    """``deque(maxlen=n)`` with drop accounting: appending past capacity
    evicts the oldest entry and counts it (optionally into a gauge), so
    truncation under overload is visible instead of silent — the fix for
    the queue's raw ``flush_log`` ring."""

    def __init__(self, maxlen: int, gauge=None):
        if maxlen < 1:
            raise ValueError("maxlen must be >= 1")
        self._d: deque = deque(maxlen=maxlen)
        self._gauge = gauge
        self.dropped = 0

    @property
    def maxlen(self) -> int:
        return self._d.maxlen

    def append(self, item) -> None:
        if len(self._d) == self._d.maxlen:
            self.dropped += 1
            if self._gauge is not None:
                self._gauge.set(float(self.dropped))
        self._d.append(item)

    def clear(self) -> None:
        self._d.clear()

    def __len__(self) -> int:
        return len(self._d)

    def __getitem__(self, i):
        return self._d[i]

    def __iter__(self):
        return iter(self._d)

    def __repr__(self) -> str:
        return (f"BoundedLog(len={len(self._d)}, "
                f"maxlen={self._d.maxlen}, dropped={self.dropped})")


def span_problems(root: Span, require: Tuple[str, ...] = (),
                  eps: float = 1e-6) -> List[str]:
    """Structural validation of one span tree — the test harness for the
    'no stage gaps' acceptance bar.  Checks every span is finished and
    non-negative, children stay inside their parent's interval and start
    in order, and each ``require`` name appears somewhere in the tree.
    Returns human-readable problems ([] == clean)."""
    problems: List[str] = []
    names: List[str] = []

    def walk(s: Span, lo: Optional[float], hi: Optional[float]) -> None:
        names.append(s.name)
        if not s.finished:
            problems.append(f"span {s.name!r} never finished")
        else:
            if s.t1 < s.t0 - eps:
                problems.append(f"span {s.name!r} ends before it starts "
                                f"({s.t0} -> {s.t1})")
            if lo is not None and (s.t0 < lo - eps or s.t1 > hi + eps):
                problems.append(
                    f"span {s.name!r} [{s.t0}, {s.t1}] escapes its "
                    f"parent [{lo}, {hi}]")
        prev = None
        for c in s.children:
            if prev is not None and c.t0 < prev - eps:
                problems.append(f"children of {s.name!r} start out of "
                                f"order at {c.name!r}")
            prev = c.t0
            if s.finished:
                walk(c, s.t0, s.t1)
            else:
                walk(c, None, None)

    walk(root, None, None)
    for r in require:
        if r not in names:
            problems.append(f"missing required span {r!r} "
                            f"(tree has {sorted(set(names))})")
    return problems
