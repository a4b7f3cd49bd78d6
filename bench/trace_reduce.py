"""Reduce a profiler trace (`.xplane.pb`) to what the per-layer metrics
read: the busy intervals of each device, the device operations sorted
into Mosaic (Pallas) kernels, collectives and the rest, and the
benchmark driver's own `TraceAnnotation` spans on the host.

`read_xplane` is the only part that knows the file format; `reduce` is
plain interval arithmetic over event lists, so tests feed it synthetic
events with known totals.
"""
from __future__ import annotations

import dataclasses
import re
from typing import NamedTuple

# opcodes of collective operations (and their async -start/-done halves)
_COLLECTIVE = re.compile(r"^(all-gather|all-reduce|collective-permute|"
                         r"reduce-scatter|all-to-all|collective-broadcast)")
# the opcode of an HLO instruction's text: "%name = <shape> opcode(..."
_OPCODE = re.compile(r" ([a-z][a-z0-9_\-]*)\(")
OP_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"  # async copies and collectives, start to done
SPAN_PREFIXES = ("bench.", "fit.")


class Op(NamedTuple):
    device: str
    name: str  # the HLO instruction's name, e.g. "fitness.12"
    start: float  # ns
    end: float  # ns
    kind: str  # "mosaic" (a Pallas kernel) | "collective" | "other"
    leaf: bool  # False for an op that encloses others (a loop, a branch)


def classify(text: str) -> tuple[str, str]:
    """(name, kind) of one device operation from its HLO text. A Pallas
    kernel lowers to a Mosaic `custom-call`."""
    name = text.split(" = ", 1)[0].lstrip("%")
    rest = text.split(" = ", 1)[1] if " = " in text else ""
    m = _OPCODE.search(" " + rest)
    opcode = m.group(1) if m else ""
    if _COLLECTIVE.match(opcode):
        return name, "collective"
    if opcode == "custom-call":
        return name, "mosaic"
    return name, "other"


def device_ops(device: str, events) -> list[Op]:
    """Ops of one device's op line from (text, start_ns, end_ns); an op
    that contains the next one (a loop, a branch) is not a leaf."""
    rows = sorted(events, key=lambda e: (e[1], -e[2]))
    out = []
    for i, (text, s, e) in enumerate(rows):
        nested = (i + 1 < len(rows) and rows[i + 1][1] < e
                  and rows[i + 1][2] <= e)
        name, kind = classify(text)
        # a collective overlaps compute; it never encloses it
        out.append(Op(device, name, s, e, kind,
                      kind == "collective" or not nested))
    return out


def read_xplane(path: str):
    """(ops, spans) from one trace file: every operation on each device's
    op line and the collectives on its async line, and the host spans
    whose names start with SPAN_PREFIXES."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops, spans = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OP_LINE:
                    ops.extend(device_ops(plane.name, (
                        (e.name, e.start_ns, e.end_ns) for e in line.events)))
                elif line.name == ASYNC_LINE:
                    ops.extend(o for o in device_ops(plane.name, (
                        (e.name, e.start_ns, e.end_ns) for e in line.events))
                        if o.kind == "collective")
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIXES):
                        spans.append((e.name, float(e.start_ns),
                                      float(e.end_ns)))
    return ops, spans


def union(intervals) -> list[tuple[float, float]]:
    """Merged, sorted, non-overlapping cover of (start, end) intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a, b):
    """Parts of the union `a` not covered by the union `b`."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


@dataclasses.dataclass
class Reduced:
    """One traced window, reduced. Times in seconds."""
    window_s: float
    devices: list
    busy_s: dict  # device -> union of its operations within the window
    kind_s: dict  # (device, kind) -> summed durations within the window
    exposed_collective_s: dict  # device -> collective time with no compute
    top_ops: list  # [[name, seconds]] summed over devices, largest first
    idle_gaps: list  # [[span name, seconds]] idle time by open driver span
    spans: list  # driver spans inside the window: (name, start_s, end_s)

    def mean(self, per_device: dict) -> float:
        return (sum(per_device.get(d, 0.0) for d in self.devices)
                / max(len(self.devices), 1))

    def kind_mean_s(self, kind: str) -> float:
        return self.mean({d: self.kind_s.get((d, kind), 0.0)
                          for d in self.devices})


def _open_span(spans, t: float) -> str:
    """Innermost driver span open at time t (the latest-started)."""
    best = None
    for name, s, e in spans:
        if s <= t < e and (best is None or s >= best[1]):
            best = (name, s)
    return best[0] if best else "outside spans"


def reduce(ops, spans, window: tuple[float, float] | None = None,
           devices: list | None = None, top: int = 10) -> Reduced:
    """Reduce operations and spans (ns) over `window`, by default the
    driver's `bench.traced` span. `devices` lists the devices the run
    used (a device with no operation counts as idle throughout)."""
    if window is None:
        w = [(s, e) for name, s, e in spans if name == "bench.traced"]
        if not w:
            raise ValueError("the trace holds no bench.traced span")
        window = w[0]
    lo, hi = window
    devs = sorted(devices if devices is not None else {o.device for o in ops})
    busy, kind_s, exposed = {}, {}, {}
    per_name: dict[str, float] = {}
    for d in devs:
        mine = [o for o in ops if o.device == d]
        cover = clip(union((o.start, o.end) for o in mine), lo, hi)
        busy[d] = length(cover) / 1e9
        for kind in ("mosaic", "collective", "other"):
            iv = [(o.start, o.end) for o in mine if o.kind == kind and o.leaf]
            kind_s[(d, kind)] = sum(e - s for s, e in clip(iv, lo, hi)) / 1e9
        coll = clip(union((o.start, o.end) for o in mine
                          if o.kind == "collective"), lo, hi)
        comp = union((o.start, o.end) for o in mine
                     if o.kind != "collective" and o.leaf)
        exposed[d] = length(subtract(coll, comp)) / 1e9
        for o in mine:
            if not o.leaf:
                continue
            c = clip([(o.start, o.end)], lo, hi)
            if c:
                per_name[o.name] = per_name.get(o.name, 0.0) + length(c) / 1e9
    inside = [(n, s, e) for n, s, e in spans
              if e > lo and s < hi and not n.startswith("bench.")]
    gaps: dict[str, float] = {}
    for d in devs:
        cover = clip(union((o.start, o.end) for o in ops if o.device == d),
                     lo, hi)
        for s, e in subtract([(lo, hi)], cover):
            # split the gap where a driver span opens or closes in it
            cuts = sorted({s, e, *(t for _, a, b in inside for t in (a, b)
                                   if s < t < e)})
            for a, b in zip(cuts, cuts[1:]):
                name = _open_span(inside, (a + b) / 2)
                gaps[name] = gaps.get(name, 0.0) + (b - a) / 1e9 / len(devs)
    return Reduced(
        window_s=(hi - lo) / 1e9, devices=devs, busy_s=busy, kind_s=kind_s,
        exposed_collective_s=exposed,
        top_ops=[[n, t] for n, t in sorted(per_name.items(),
                                           key=lambda kv: -kv[1])[:top]],
        idle_gaps=[[n, t] for n, t in sorted(gaps.items(),
                                             key=lambda kv: -kv[1])[:top]],
        spans=[(n, s / 1e9, e / 1e9) for n, s, e in inside])
