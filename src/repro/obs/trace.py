"""Span tracer + shared event bus (deterministic step-clock primary).

The primary clock is the **step clock**: whoever owns the tracer calls
:meth:`Tracer.set_step` once per engine/fleet step, and every span/event
records ``(step, seq)`` where ``seq`` is a monotonically increasing
per-tracer ordinal.  Both are pure functions of the (seeded) serving
schedule, so two seeded runs — or an uninterrupted run vs a
checkpoint-restored one — emit **bitwise-identical JSONL traces**.
Wall-clock timing is opt-in (``wall_clock=True``) and lands only in
``wall_*``-prefixed fields, which readers (and the determinism tests)
strip.

Entries are plain dicts with a stable field order:

* spans:  ``{"kind": "span", "seq", "name", "step", "end_step", attrs...}``
* events: ``{"kind": "event", "seq", "type", "step", attrs...}``

The :class:`EventBus` is the **shared event seam** the fleet, the serving
engines, and the recal schedulers all publish on: entries carry the same
``step``/``type`` field names everywhere and are tagged with ``chip`` /
``ramp`` ids where applicable, replacing the ad-hoc per-object event
lists (compat accessors on ``FleetEngine.events`` /
``RecalScheduler.events`` keep the old views working).  A bus can forward
onto a tracer so bus events land in the exported JSONL timeline.

Profiler spans.  Every :meth:`Tracer.span` also opens a
``jax.profiler.TraceAnnotation`` for its duration, recorded or not, so
the program's spans land on the profiler's clock beside the device ops
whenever a profiler session runs (and cost about a microsecond each when
none does).  This module names them all: a JSONL span keeps its short
name and takes the profiler name ``PROFILE_NAMES`` gives it;
:func:`annotate` and :func:`annotate_step` open the profiler-only
sub-spans, which leave no JSONL entry.

Compile counter.  The first :func:`watch_compiles` registers one
``jax.monitoring`` listener per process, which fires only when JAX
traces, lowers or compiles a program (or reads one from the persistent
cache), never in steady state.  ``watch_compiles`` publishes its totals
as counters in a ``MetricsRegistry``; :func:`compile_totals` reads them
at a past ``time.perf_counter`` instant.
"""

from __future__ import annotations

import bisect
import collections
import json
import threading
import time
import weakref
from typing import Dict, List, Optional, Tuple

import jax
from jax.profiler import StepTraceAnnotation, TraceAnnotation

# -- profiler span names ------------------------------------------------
# PERF.md section 3 names the metric that reads each.
SERVE_STEP = "serve.step"            # all of ServingEngine.step()
SERVE_ADMIT = "serve.admit"          # an admission wave
SERVE_PREFILL = "serve.prefill"      # prefill calls of one wave
SERVE_SCATTER = "serve.scatter"      # prefilled rows into their slots
SERVE_DECODE = "serve.decode"        # one batch decode step, inputs to
#                                      bookkeeping:
DECODE_INPUTS = "serve.decode.inputs"          # host -> device inputs
DECODE_DISPATCH = "serve.decode.dispatch"      # the jitted call
DECODE_SYNC = "serve.decode.sync"              # waiting for the tokens
DECODE_BOOKKEEP = "serve.decode.bookkeep"      # per-row host work
SERVE_WARMUP = "serve.warmup"        # ServingEngine.warmup()
SERVE_COMPILE = "serve.compile"      # lowering and compiling one program

# JSONL span name -> profiler name (others keep their own name)
PROFILE_NAMES = {"admit": SERVE_ADMIT, "prefill": SERVE_PREFILL,
                 "decode": SERVE_DECODE}


def annotate(name: str) -> TraceAnnotation:
    """A profiler-only span (no JSONL entry): ``with annotate(NAME):``."""
    return TraceAnnotation(name)


def annotate_step(name: str, step: int) -> StepTraceAnnotation:
    """A profiler-only span that also marks step ``step`` for the
    profiler's step view."""
    return StepTraceAnnotation(name, step_num=step)


class Tracer:
    """Append-only span/event recorder on a deterministic step clock."""

    def __init__(self, *, enabled: bool = True, wall_clock: bool = False):
        self.enabled = enabled
        self.wall_clock = wall_clock
        self.entries: List[dict] = []
        self.step = 0
        self.seq = 0

    def set_step(self, step: int) -> None:
        self.step = int(step)

    def _next_seq(self) -> int:
        self.seq += 1
        return self.seq

    def event(self, type: str, **attrs) -> None:
        """One point on the timeline at the current step."""
        if not self.enabled:
            return
        entry = {"kind": "event", "seq": self._next_seq(), "type": type,
                 "step": self.step}
        if self.wall_clock:
            entry["wall_s"] = time.time()
        entry.update(attrs)
        self.entries.append(entry)

    def span(self, name: str, **attrs) -> "_Span":
        """Context manager recording a ``[start step, end step]`` span.

        The entry is appended at *exit* (so a trace is a valid timeline
        even mid-span) with any attrs added via :meth:`_Span.set`.
        """
        return _Span(self, name, attrs)

    # -- state / export ------------------------------------------------

    def counters(self) -> dict:
        """The replayable clock state (rides in checkpoints so a restored
        deployment's trace continues with the exact seq/step ordinals)."""
        return {"step": self.step, "seq": self.seq}

    def restore_counters(self, d: dict) -> None:
        self.step = int(d.get("step", 0))
        self.seq = int(d.get("seq", 0))

    def to_jsonl(self) -> str:
        return "".join(json.dumps(e, sort_keys=False) + "\n"
                       for e in self.entries)

    def write_jsonl(self, path: str, *, append: bool = False) -> None:
        with open(path, "a" if append else "w") as f:
            f.write(self.to_jsonl())

    def drain(self) -> List[dict]:
        """Pop all recorded entries (long-running exporters flush with
        this so the in-memory trace stays bounded)."""
        out, self.entries = self.entries, []
        return out


class _Span:
    def __init__(self, tracer: Tracer, name: str, attrs: dict):
        self._t = tracer
        self._name = name
        self._attrs = dict(attrs)
        self._start_step = 0
        self._start_wall = 0.0
        self._note = None

    def set(self, **attrs) -> None:
        self._attrs.update(attrs)

    def __enter__(self) -> "_Span":
        self._note = TraceAnnotation(PROFILE_NAMES.get(self._name,
                                                       self._name))
        self._note.__enter__()
        self._start_step = self._t.step
        if self._t.wall_clock:
            self._start_wall = time.time()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self._note.__exit__(exc_type, exc, tb)
        t = self._t
        if not t.enabled:
            return
        entry = {"kind": "span", "seq": t._next_seq(), "name": self._name,
                 "step": self._start_step, "end_step": t.step}
        if t.wall_clock:
            now = time.time()
            entry["wall_s"] = self._start_wall
            entry["wall_dur_s"] = now - self._start_wall
        entry.update(self._attrs)
        t.entries.append(entry)


class EventBus:
    """The shared, serializable event stream of a deployment.

    ``emit`` appends ``{"step", "type", **tags}`` (``src`` names the
    publishing layer: "fleet", "engine", "sched") and mirrors the entry
    onto the attached tracer so exported traces carry the full
    cross-layer timeline.  The list is plain JSON — fleet checkpoints
    save and restore it verbatim.
    """

    def __init__(self, tracer: Optional[Tracer] = None):
        self.events: List[dict] = []
        self.tracer = tracer

    def emit(self, type: str, *, step: int, src: str = "fleet",
             **tags) -> dict:
        entry = {"step": int(step), "type": type, "src": src, **tags}
        self.events.append(entry)
        if self.tracer is not None:
            self.tracer.event(type, src=src,
                              **{k: v for k, v in tags.items()})
        return entry

    def view(self, *, src: Optional[str] = None,
             chip: Optional[str] = None) -> List[dict]:
        """Filtered read (compat accessors build their old-shape lists
        from this)."""
        out = self.events
        if src is not None:
            out = [e for e in out if e.get("src") == src]
        if chip is not None:
            out = [e for e in out if e.get("chip") == chip]
        return list(out)


WALL_FIELDS = ("wall_s", "wall_dur_s")


def strip_wall(entries) -> List[dict]:
    """Entries minus the wall-clock fields — the determinism-comparable
    projection of a trace (used by tests and ``repro.obs.replay``)."""
    return [{k: v for k, v in e.items() if k not in WALL_FIELDS}
            for e in entries]


def read_jsonl(path: str) -> List[dict]:
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                out.append(json.loads(line))
    return out


# -- compile counter ----------------------------------------------------

# jax.monitoring events whose time counts as compiling: tracing to a
# jaxpr, lowering, and the backend compile, which holds the persistent
# cache's read on a hit.  One backend event is one executable built.
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
_COMPILE_EVENTS = frozenset({"/jax/core/compile/jaxpr_trace_duration",
                             "/jax/core/compile/jaxpr_to_mlir_module_duration",
                             _BACKEND_EVENT})


class _CompileWatch:
    """Process-wide totals of programs built and seconds spent building
    them, with a log of the totals after each event on the
    ``time.perf_counter`` clock.  Tracing nests (a jitted function traces
    the jitted functions it calls), so the seconds are the union of the
    events' intervals, not their sum."""

    def __init__(self):
        self.lock = threading.Lock()
        self.programs = 0
        self.seconds = 0.0
        self.spans: List[List[float]] = []     # counted intervals, by end
        self.log = collections.deque([(time.perf_counter(), 0, 0.0)],
                                     maxlen=4096)
        self.registries = weakref.WeakSet()
        jax.monitoring.register_event_time_span_listener(self.on_span)

    def on_span(self, event: str, start: float, end: float, **_) -> None:
        if event not in _COMPILE_EVENTS:
            return
        with self.lock:
            inner = 0.0
            while self.spans and self.spans[-1][0] >= start:
                s, e = self.spans.pop()
                inner += e - s
            if self.spans and self.spans[-1][1] > start:
                start = self.spans[-1][1]
            added = max(end - start - inner, 0.0)
            self.spans.append([start, end])
            del self.spans[:-64]
            programs = int(event == _BACKEND_EVENT)
            self.programs += programs
            self.seconds += added
            self.log.append((time.perf_counter(), self.programs,
                             self.seconds))
            for reg in list(self.registries):
                reg.counter("compile.programs").inc(programs)
                reg.counter("compile.seconds").inc(added)


_WATCH: Optional[_CompileWatch] = None
_WATCH_LOCK = threading.Lock()


def watch_compiles(registry) -> None:
    """Publish the compile counter in ``registry`` from now on:
    ``compile.programs`` (executables built: backend compiles and
    persistent-cache reads) and ``compile.seconds`` (tracing, lowering,
    compiling and cache reading).  The counters are process-wide and
    unlabelled: a registry shared by a fleet's chips counts each compile
    once."""
    global _WATCH
    registry.counter("compile.programs")
    registry.counter("compile.seconds")
    with _WATCH_LOCK:
        if _WATCH is None:
            _WATCH = _CompileWatch()
    with _WATCH.lock:
        _WATCH.registries.add(registry)


def compile_totals(at: Optional[float] = None
                   ) -> Optional[Tuple[int, float]]:
    """(programs, seconds) built in this process up to the
    ``time.perf_counter`` instant ``at`` (default now), counted from the
    first :func:`watch_compiles`; None before that, or when ``at`` lies
    before the oldest entry the log still holds."""
    if _WATCH is None:
        return None
    with _WATCH.lock:
        log = list(_WATCH.log)
    if at is None:
        return log[-1][1], log[-1][2]
    i = bisect.bisect_right([t for t, _, _ in log], at)
    if i == 0:
        return None
    return log[i - 1][1], log[i - 1][2]
