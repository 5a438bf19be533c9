"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer readers
read.

    python -m bench.trace_reduce <file.xplane.pb> [n_devices]

For each device plane (``/device:TPU:<i>``): the union of the intervals
in which an op ran (busy); device self time (an op's time less that of
the ops nested in it) by op kind, which is the op's HLO
opcode (``sort``, ``scatter``, ``gather``, ``all-to-all``, ``collective``,
``fusion`` — a scatter or gather that XLA fused counts here — ``copy``,
``custom`` for Pallas kernels, ``other``); each Pallas kernel's time,
calls and the bytes of its operands and results (from the shapes in the op's HLO text), and each
jitted program's time and calls.  Across devices: the ten ops that took
most time, and the ten longest idle gaps of device 0, each named by the
benchmark's host span that was open during it (``none`` if none was).
"""
from __future__ import annotations

import collections
import json
import re
import sys

from bench.flops import shape_bytes

SPAN_NAMES = ("pipeline_call", "engine.step", "submit", "feature_lookup",
              "host_wait")
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
                "collective-permute")
_MODULE_ID = re.compile(r"\(\d+\)$")


def parse_op(text: str) -> tuple[str, str, str]:
    """``(name, opcode, kind)`` of a device op event, whose name on the TPU
    is the op's HLO text: ``%sort.6 = (s32[8]{0}, ...) sort(...), ...``."""
    if not text.startswith("%") or " = " not in text:
        return text, text, "other"
    name, rest = text[1:].split(" = ", 1)
    if rest.startswith("("):                       # tuple result type
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        rest = rest[i + 1:].lstrip()
    else:
        rest = rest.split(" ", 1)[1] if " " in rest else ""
    opcode = rest.split("(", 1)[0]
    if opcode == "custom-call":
        kind = "custom" if 'custom_call_target="tpu_custom_call"' in text \
            else "other"
    elif opcode in ("sort", "scatter", "gather", "all-to-all", "fusion",
                    "copy"):
        kind = opcode
    elif opcode in _COLLECTIVES or opcode.split("-start")[0] in _COLLECTIVES:
        kind = "collective"
    elif opcode.startswith("all-to-all"):
        kind = "all-to-all"
    else:
        kind = "other"
    return name, opcode, kind


def kernel_name(op_name: str) -> str:
    """A Pallas kernel's name from its op's (``_radix_histogram_ranks.2``)."""
    return re.sub(r"\.\d+$", "", op_name)


def _union(intervals):
    """Merged ``(start, end)`` intervals, sorted."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _device_planes(pd):
    planes = [p for p in pd.planes if p.name.startswith("/device:TPU:")
              and p.name[len("/device:TPU:"):].isdigit()]
    return sorted(planes, key=lambda p: int(p.name.rsplit(":", 1)[1]))


def self_times(events):
    """``[(event, self seconds)]``: an op's duration less that of the ops
    nested in it (a ``while`` holds its body's ops), so no time counts
    twice."""
    evs = sorted(events, key=lambda e: (e.start_ns, -e.duration_ns))
    selfs = [e.duration_ns for e in evs]
    stack = []
    for i, e in enumerate(evs):
        end = e.start_ns + e.duration_ns
        while stack and evs[stack[-1]].start_ns + \
                evs[stack[-1]].duration_ns < end:
            stack.pop()
        if stack:
            selfs[stack[-1]] -= e.duration_ns
        stack.append(i)
    return [(e, max(0.0, s) * 1e-9) for e, s in zip(evs, selfs)]


def _line(plane, name):
    for ln in plane.lines:
        if ln.name == name:
            return ln
    return None


def _host_spans(pd):
    spans = []
    for p in pd.planes:
        if not p.name.startswith("/host:"):
            continue
        for ln in p.lines:
            for ev in ln.events:
                if ev.name in SPAN_NAMES:
                    spans.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                  ev.name))
    return spans


def _name_gap(a, b, spans) -> str:
    best, name = 0.0, "none"
    for s, e, n in spans:
        ov = min(b, e) - max(a, s)
        if ov > best:
            best, name = ov, n
    return name


def reduce(path, n_devices: int | None = None, window_s: float | None = None):
    """The reduced trace as a dict (see the module docstring)."""
    import jax
    pd = jax.profiler.ProfileData.from_file(str(path))
    planes = _device_planes(pd)
    if n_devices is not None:
        planes = planes[:n_devices]
    if not planes:
        return None
    spans = _host_spans(pd)
    devices, top = [], collections.Counter()
    gaps = []
    t_lo, t_hi = float("inf"), float("-inf")
    for i, plane in enumerate(planes):
        ops_line = _line(plane, "XLA Ops")
        mod_line = _line(plane, "XLA Modules")
        kinds = collections.Counter()
        kernels: dict[str, list] = {}
        intervals = []
        events = list(ops_line.events) if ops_line is not None else []
        for ev, secs in self_times(events):
            name, _, kind = parse_op(ev.name)
            kinds[kind] += secs
            top[name] += secs
            intervals.append((ev.start_ns, ev.start_ns + ev.duration_ns))
            if kind == "custom":
                k = kernels.setdefault(kernel_name(name), [0.0, 0, 0])
                k[0] += secs
                k[1] += 1
                k[2] += shape_bytes(ev.name.split(", custom_call_target")[0])
        programs: dict[str, list] = {}
        for ev in (mod_line.events if mod_line is not None else ()):
            p = programs.setdefault(_MODULE_ID.sub("", ev.name), [0.0, 0])
            p[0] += ev.duration_ns * 1e-9
            p[1] += 1
        busy = _union(intervals)
        if busy:
            t_lo, t_hi = min(t_lo, busy[0][0]), max(t_hi, busy[-1][1])
        devices.append({
            "busy_s": sum(b - a for a, b in busy) * 1e-9,
            "ops": dict(kinds), "kernels": kernels, "programs": programs})
        if i == 0:
            gaps = [(a, b) for (_, a), (b, _) in zip(busy, busy[1:])]
    named = sorted(((b - a) * 1e-9, _name_gap(a, b, spans)) for a, b in gaps)
    if window_s is None:
        window_s = (t_hi - t_lo) * 1e-9 if t_hi > t_lo else 0.0
    return {
        "window_s": window_s,
        "busy_s": sum(d["busy_s"] for d in devices) / len(devices),
        "devices": devices,
        "top_ops": [[n, s] for n, s in top.most_common(10)],
        "idle_gaps": [[n, s] for s, n in named[::-1][:10]],
    }


if __name__ == "__main__":
    n = int(sys.argv[2]) if len(sys.argv) > 2 else None
    print(json.dumps(reduce(sys.argv[1], n), indent=1))
