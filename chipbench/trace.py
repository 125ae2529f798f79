"""From a profiler trace to the benchmark's device numbers.

``collect`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote into a
small recorded trace: for each TPU plane, the events of its ``XLA Ops``
line as ``[name, class, start_ns, duration_ns]``, and the benchmark's own
host annotations. On a TPU an event's name is the HLO instruction's text
(``%cb_coo_spmv_batched.1 = f32[...] custom-call(...), ...``); ``event``
shortens it to the instruction's name and opcode and classifies it:
``pallas`` (a ``tpu_custom_call`` or a kernel the program names),
``collective``, or ``xla`` (every other operation).

``reduce`` turns a recorded trace into busy time, device time by class,
the top device operations and the longest idle gaps, each gap labelled by
the host annotation that overlaps it most. A ``while`` loop is an event
that contains its body's events on the same line, so classes and top
operations count each event's self time: its duration less its
children's. Keeping the recorded form lets a test check the reduction on
a trace kept in the repository.
"""
from __future__ import annotations

import collections
import pathlib
import re

# Host spans the harness writes with ``jax.profiler.TraceAnnotation``
# inside the traced window (set-up and the reference run outside it).
ANNOTATIONS = ("dispatch", "wait")

COLLECTIVES = r"(all-reduce|reduce-scatter|all-gather|collective-permute|all-to-all)"
COLLECTIVE = re.compile(rf"\b{COLLECTIVES}(-start|-done)?\(")
COLLECTIVE_NAME = re.compile(rf"^{COLLECTIVES}")

TOP = 10


def _opcode(text: str) -> str:
    """The opcode of an HLO instruction's text, or '' where it has none."""
    eq = text.find(" = ")
    if eq < 0:
        return ""
    i = eq + 3
    if text.startswith("(", i):  # a tuple shape: skip to its closing paren
        depth = 0
        for j in range(i, len(text)):
            depth += {"(": 1, ")": -1}.get(text[j], 0)
            if depth == 0:
                i = j + 1
                break
    else:
        sp = text.find(" ", i)
        i = len(text) if sp < 0 else sp
    m = re.match(r"\s*([A-Za-z][\w-]*)\(", text[i:])
    return m.group(1) if m else ""


def event(text: str, kernels: set[str]) -> tuple[str, str]:
    """``(short name, class)`` of one device event's name."""
    m = re.match(r"%?([^\s=]+)", text)
    name = m.group(1) if m else text
    op = _opcode(text)
    short = name if not op or name.split(".")[0] == op else f"{name} {op}"
    base = re.sub(r"(\.\d+)+$", "", name)
    if 'custom_call_target="tpu_custom_call"' in text or base in kernels:
        return short, "pallas"
    if (COLLECTIVE.search(text) or COLLECTIVE.search(op + "(")
            or COLLECTIVE_NAME.match(name)):
        return short, "collective"
    return short, "xla"


def collect(logdir, kernels: set[str]) -> dict:
    """The recorded trace of the newest profile under ``logdir``."""
    import jax

    paths = sorted(pathlib.Path(logdir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not paths:
        return {"device": {}, "host": []}
    data = jax.profiler.ProfileData.from_file(str(paths[-1]))
    device, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            device[plane.name] = [
                [*event(ev.name, kernels), float(ev.start_ns),
                 float(ev.duration_ns)]
                for line in plane.lines if line.name == "XLA Ops"
                for ev in line.events]
        elif plane.name.startswith("/host:"):
            host += [[ev.name, float(ev.start_ns), float(ev.duration_ns)]
                     for line in plane.lines for ev in line.events
                     if ev.name in ANNOTATIONS]
    return {"device": device, "host": host}


def _self_times(events):
    """Each event's duration less the durations of the events nested
    directly inside it (events of one line nest or follow each other)."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][2], -events[i][3]))
    self_t = [e[3] for e in events]
    stack = []
    for i in order:
        s = events[i][2]
        while stack and events[stack[-1]][2] + events[stack[-1]][3] <= s:
            stack.pop()
        if stack:
            self_t[stack[-1]] -= events[i][3]
        stack.append(i)
    return self_t


def _merge(intervals):
    """Union of [start, end) intervals, sorted and merged."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _label(gap, host) -> str:
    """The host annotation that overlaps ``gap`` most, or ``none``."""
    best, label = 0.0, "none"
    for name, s, d in host:
        ov = min(gap[1], s + d) - max(gap[0], s)
        if ov > best:
            best, label = ov, name
    return label


def reduce(rec: dict) -> dict | None:
    """Device numbers of a recorded trace, or None where it holds no
    device operation. Seconds are averaged over the TPU planes."""
    planes = {k: v for k, v in rec["device"].items() if v}
    if not planes:
        return None
    host = rec["host"]
    marks = [(s, s + d) for n, s, d in host if n in ("dispatch", "wait")]
    class_s = collections.Counter()
    op_s = collections.Counter()
    busy = 0.0
    gaps = []
    for i, (_, events) in enumerate(sorted(planes.items())):
        for ev, t in zip(events, _self_times(events)):
            class_s[ev[1]] += t * 1e-9
            op_s[ev[0]] += t * 1e-9
        merged = _merge([(e[2], e[2] + e[3]) for e in events])
        busy += sum(e - s for s, e in merged) * 1e-9
        if i == 0:
            lo = min([m[0] for m in marks] + [merged[0][0]])
            hi = max([m[1] for m in marks] + [merged[-1][1]])
            edges = [lo] + [x for iv in merged for x in iv] + [hi]
            gaps = [(edges[j], edges[j + 1]) for j in range(0, len(edges), 2)
                    if edges[j + 1] > edges[j]]
    k = len(planes)
    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "chips": k,
        "busy_s": busy / k,
        "class_s": {c: class_s[c] / k for c in ("pallas", "xla", "collective")},
        "device_ops": [[n, t / k] for n, t in op_s.most_common(TOP)],
        "idle_gaps": [[_label(g, host), (g[1] - g[0]) * 1e-9]
                      for g in gaps[:TOP]],
    }
