"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

    device ops      every event on a TPU device plane's "XLA Ops" line:
                    (name, start_ns, duration_ns, device)
    programs        every event on its "XLA Modules" line (one per
                    executable run), same fields
    annotations     the harness's ``TraceAnnotation`` spans on the host
                    planes: (name, start_ns, duration_ns)
    window          the span named ``WINDOW``, which the harness wraps
                    round the traced engine steps
    busy_s          union of the device op intervals inside the window,
                    averaged over the devices
    gaps            the idle intervals between busy ones inside the
                    window, each labelled with the innermost harness span
                    that covers its middle (or "none")

Host and device events share the profiler's clock.
"""

from __future__ import annotations

import glob
import os
import re

WINDOW = "bench.traced"
SPANS = ("bench.generate", "bench.submit", "engine.step", "bench.reset",
         "bench.reference")


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def reduce_planes(planes) -> dict:
    """``planes``: iterable of objects with ``name`` and ``lines`` (each
    with ``name`` and ``events`` having ``name``, ``start_ns``,
    ``duration_ns``), as ``jax.profiler.ProfileData`` gives them."""
    ops, programs, notes = [], [], []
    for plane in planes:
        if plane.name.startswith("/device:TPU:"):
            dev = plane.name
            for line in plane.lines:
                dst = {"XLA Ops": ops, "XLA Modules": programs}.get(line.name)
                if dst is None:
                    continue
                for e in line.events:
                    dst.append((e.name, float(e.start_ns),
                                float(e.duration_ns), dev))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW or e.name in SPANS:
                        notes.append((e.name, float(e.start_ns),
                                      float(e.duration_ns)))
    wins = [n for n in notes if n[0] == WINDOW]
    if not wins:
        raise ValueError(f"trace has no {WINDOW!r} span")
    w0 = min(n[1] for n in wins)
    w1 = max(n[1] + n[2] for n in wins)
    devices = sorted({o[3] for o in ops})
    busy_ns, gaps = 0.0, []
    for dev in devices:
        ivs = _union((max(s, w0), min(s + d, w1)) for _, s, d, dv in ops
                     if dv == dev and s + d > w0 and s < w1)
        busy_ns += sum(e - s for s, e in ivs)
        edges = [w0] + [x for iv in ivs for x in iv] + [w1]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps.append((s, e - s, _label(notes, 0.5 * (s + e))))
    gaps.sort(key=lambda g: -g[1])
    return {
        "ops": [o for o in ops if o[1] + o[2] > w0 and o[1] < w1],
        "programs": [p for p in programs if p[1] + p[2] > w0 and p[1] < w1],
        "annotations": notes,
        "window_ns": (w0, w1),
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy_ns * 1e-9 / max(len(devices), 1),
        "devices": devices,
        "gaps": gaps,
    }


def _label(notes, t):
    inside = [n for n in notes if n[0] != WINDOW and n[1] <= t <= n[1] + n[2]]
    if not inside:
        return "none"
    return min(inside, key=lambda n: n[2])[0]          # the innermost span


def reduce_file(path: str) -> dict:
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(path).planes)


def _container(name: str) -> bool:
    """A while or conditional op: its event spans the ops of its body."""
    return " while(" in name or " conditional(" in name


def breakdown(red: dict, top: int = 10) -> dict:
    """Top device ops by total time (loops and conditionals, whose events
    hold their bodies' ops, left out), and the longest idle gaps with what
    the host was doing in them (seconds)."""
    tot = {}
    for name, _, dur, _ in red["ops"]:
        if not _container(name):
            tot[name] = tot.get(name, 0.0) + dur
    n_dev = max(len(red["devices"]), 1)
    ops = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v * 1e-9 / n_dev] for k, v in ops],
            "idle_gaps": [[label, dur * 1e-9]
                          for _, dur, label in red["gaps"][:top]]}


def program_events(red: dict, marker: str):
    """Executable runs whose program name contains ``marker``."""
    return [p for p in red["programs"] if marker in p[0]]


_OPERAND = re.compile(r"^\s*([a-z0-9]+)\[([0-9,]*)\](\{[^ ]*\})?")
_NAME = re.compile(r"%([^\s,()]+)\s*$")


def _typed(text: str):
    """``bf16[256,2048]{1,0:T(8,128)(2,1)S(1)} %x`` -> (dtype, shape,
    in_vmem): ``S(1)`` in the layout is the TPU's on-chip vector memory,
    where XLA may stage a kernel's operand before the call."""
    m = _OPERAND.match(text)
    if m is None:
        return None
    dims = tuple(int(d) for d in m.group(2).split(",") if d)
    return m.group(1), dims, "S(1)" in (m.group(3) or "")


def _balanced(text: str, start: int):
    """The top-level comma-separated parts of the bracketed list that
    opens at ``text[start]``, and the index just past its close."""
    depth, cur, parts = 0, "", []
    for i in range(start + 1, len(text)):
        ch = text[i]
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            if depth == 0:
                parts.append(cur)
                return parts, i + 1
            depth -= 1
        if ch == "," and depth == 0:
            parts.append(cur)
            cur = ""
        else:
            cur += ch
    return parts, len(text)


def hlo_op(text: str):
    """An op's HLO text, as the TPU trace gives it for its name
    (``%name = type opcode(operands), attributes``) -> (name, opcode,
    [(operand name, in_vmem)]), or None."""
    if not text.startswith("%") or " = " not in text:
        return None
    name, rest = text[1:].split(" = ", 1)
    i = _balanced(rest, 0)[1] if rest.startswith("(") else rest.find(" ")
    j = rest.find("(", i)
    if i < 0 or j < 0:
        return None
    args = []
    for a in _balanced(rest, j)[0]:
        m = _NAME.search(a)
        if m:
            args.append((m.group(1), "S(1)" in a.rsplit("%", 1)[0]))
    return name, rest[i:j].strip(), args


MOVES = {"copy", "copy-start", "copy-done", "reshape", "bitcast",
         "transpose", "broadcast", "pad", "slice", "dynamic-slice",
         "dynamic-update-slice", "concatenate", "constant"}


def _moves_data(name: str, opcode: str) -> bool:
    """An op that only moves or lays out data: a movement opcode, or a
    fusion whose name lists movement ops alone
    (``dynamic-slice_bitcast_fusion.8``)."""
    if opcode != "fusion":
        return opcode in MOVES
    words = name.rsplit(".", 1)[0].split("_")
    words = words[:-1] if words[-1] == "fusion" else words
    return bool(words) and all(w in MOVES for w in words)


def custom_call_result(op_name: str):
    """The result type of a Pallas kernel's op, as ``_typed`` gives it."""
    return _typed(op_name.split(" = ", 1)[1]) if " = " in op_name else None


def custom_call_operands(op_name: str):
    """The operand types of a Pallas kernel's op, read from the HLO text
    the TPU trace gives as the op's name: ``[(dtype, shape, in_vmem),
    ...]``, or None when the op is not a ``tpu_custom_call``.  The kernels
    carry no name of their own in the trace; their operand signature tells
    them apart."""
    if 'custom_call_target="tpu_custom_call"' not in op_name:
        return None
    start = op_name.index("custom-call(") + len("custom-call")
    out = [_typed(p) for p in _balanced(op_name, start)[0]]
    return None if None in out else out


def kernel_events(red: dict, match):
    """Device ops of the Pallas kernels whose operand signature satisfies
    ``match(operands)``: -> [(operands, result, start_ns, duration_ns,
    HLO text, device)]."""
    out = []
    for name, start, dur, dev in red["ops"]:
        ops = custom_call_operands(name)
        if ops is not None and match(ops):
            out.append((ops, custom_call_result(name), start, dur, name, dev))
    return out


def staged_ops(red: dict, events):
    """The ops that staged the kernel calls ``events`` (as
    ``kernel_events`` gives them) into vector memory: for each operand
    with the ``S(1)`` layout, the op that made it, if it only moves data
    (a slice, copy, reshape or pad), and so on back through its own
    staged operands.  -> [(name, duration_ns)].  Charged to the kernel,
    such ops make its time the same whether XLA or the kernel itself
    reads the bytes from HBM."""
    by_name, seen, out = {}, set(), []
    for text, start, dur, dev in red["ops"]:
        if text.startswith("%") and " = " in text:
            by_name.setdefault((text[1:text.index(" = ")], dev), []).append(
                (start, dur, text))
    for _, _, start, _, text, dev in events:
        todo = [a for a, vmem in hlo_op(text)[2] if vmem]
        while todo:
            runs = [r for r in by_name.get((todo.pop(), dev), ())
                    if r[0] < start]
            if not runs:
                continue
            t0, dur, op_text = max(runs, key=lambda r: r[0])
            name, opcode, args = hlo_op(op_text)
            if (name, t0) in seen or not _moves_data(name, opcode):
                continue
            seen.add((name, t0))
            out.append((name, dur))
            todo += [a for a, vmem in args if vmem]
    return out


def staged_seconds(red: dict, events) -> float:
    """Device seconds of ``staged_ops``."""
    return sum(d for _, d in staged_ops(red, events)) * 1e-9
