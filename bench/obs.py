"""Readings of the program's own instrumentation (``repro.obs``).

    compile_totals   the program's process-wide compile counter at a
                     ``time.perf_counter`` instant: (programs built,
                     seconds tracing, lowering, compiling or reading the
                     persistent cache)
    serve_spans      the program's ``serve.*`` profiler spans on the host
                     planes of a trace: (name, start_ns, duration_ns)
    idle_by_span     the device's idle time in the traced window, split
                     at span edges and charged to the innermost span
                     (harness or program) that covers each piece
    decode_host_ms   host time of a decode-only engine step outside its
                     wait for the device
    engine_idle_share
                     device-idle share of the traced window that falls
                     inside engine steps

Each returns None where the program publishes nothing to read, as a
program without these spans or this counter does.

``compile_totals`` feeds ``compiles_in_window`` and ``setup_compile_s``.
The span readings need the host spans, which ``trace.reduce_planes``
does not keep and ``run.py`` deletes with the trace; PERF.md section 7
says what wires them into a metric.
"""

from __future__ import annotations

import importlib

from bench import trace as T

PREFIX = "serve."
STEP = "serve.step"
DECODE = "serve.decode"
ADMIT = "serve.admit"
SYNC = "serve.decode.sync"


def compile_totals(at: float):
    """``repro.obs.trace.compile_totals(at)``, or None where the program
    has no compile counter or its log does not reach back to ``at``."""
    try:
        mod = importlib.import_module("repro.obs.trace")
    except ImportError:
        return None
    fn = getattr(mod, "compile_totals", None)
    return None if fn is None else fn(at)


def serve_spans(planes):
    """``planes`` as ``trace.reduce_planes`` takes them -> the program's
    spans on the host planes, [(name, start_ns, duration_ns)] by start."""
    out = [(e.name, float(e.start_ns), float(e.duration_ns))
           for plane in planes if plane.name.startswith("/host:")
           for line in plane.lines for e in line.events
           if e.name.startswith(PREFIX)]
    return sorted(out, key=lambda s: (s[1], -s[2]))


def _inside(spans, outer, name):
    _, s0, d0 = outer
    return [s for s in spans if s[0] == name and s0 <= s[1]
            and s[1] + s[2] <= s0 + d0]


def _steps(red, spans):
    """``serve.step`` spans that lie wholly inside the traced window."""
    w0, w1 = red["window_ns"]
    return [s for s in spans if s[0] == STEP and w0 <= s[1]
            and s[1] + s[2] <= w1]


def decode_host_ms(red, spans):
    """Mean, over the traced ``serve.step`` spans that hold a
    ``serve.decode`` and no ``serve.admit``, of the step's duration less
    its ``serve.decode.sync``, ms."""
    host = [st[2] - sum(s[2] for s in _inside(spans, st, SYNC))
            for st in _steps(red, spans)
            if _inside(spans, st, DECODE) and not _inside(spans, st, ADMIT)]
    return sum(host) * 1e-6 / len(host) if host else None


def _overlap(a0, a1, intervals):
    return sum(max(min(a1, e) - max(a0, s), 0.0) for s, e in intervals)


def engine_idle_share(red, spans):
    """Device-idle time in the traced window that overlaps a
    ``serve.step`` span, over the window, %, averaged over the chips."""
    steps = [(s, s + d) for _, s, d in _steps(red, spans)]
    if not steps or red["window_s"] <= 0:
        return None
    idle = sum(_overlap(s, s + d, steps) for s, d, _ in red["gaps"])
    n_dev = max(len(red["devices"]), 1)
    return 100.0 * idle * 1e-9 / n_dev / red["window_s"]


def idle_by_span(red, spans, top: int = 10):
    """Idle seconds in the traced window per innermost covering span:
    each idle gap is cut at the edges of the spans over it, and each
    piece is charged to the shortest span (the harness's or the
    program's) that covers it, or to "none".  -> [[name, seconds]],
    longest first, at most ``top``; None without program spans."""
    if not spans:
        return None
    notes = [n for n in red["annotations"] if n[0] != T.WINDOW] + spans
    n_dev = max(len(red["devices"]), 1)
    tot = {}
    for g0, dur, _ in red["gaps"]:
        g1 = g0 + dur
        over = [n for n in notes if n[1] < g1 and n[1] + n[2] > g0]
        cuts = sorted({g0, g1} | {x for _, s, d in over for x in (s, s + d)
                                   if g0 < x < g1})
        for a, b in zip(cuts, cuts[1:]):
            cover = [n for n in over if n[1] <= a and b <= n[1] + n[2]]
            name = min(cover, key=lambda n: n[2])[0] if cover else "none"
            tot[name] = tot.get(name, 0.0) + (b - a)
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
    return [[k, v * 1e-9 / n_dev] for k, v in ranked]
