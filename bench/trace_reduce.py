"""Profiler trace -> device busy time, kernel times and labelled idle gaps.

Reads the `.xplane.pb` that `jax.profiler` writes, with nothing but JAX:

  * device planes are `/device:TPU:<n>`; their `XLA Ops` line holds one
    event per operation that ran on the device;
  * an event's name is its HLO instruction (`%ext_lut_pairs_kernel.1 =
    f32[...] custom-call(...)`); its stable name is the instruction's
    name without the instance number (`fusion`, `copy`, `while`).  A
    Pallas kernel is named by its kernel function instead, read from the
    op's `tf_op` path in the plane's event metadata
    (`jit(sharded_search)/.../jit(adc_topk_tiles_kernel)/while/body/
    closed_call/pallas_call`: the innermost `jit(...)`), since the
    instruction of a kernel called inside a loop body is a generic
    `closed_call`;
  * operations nest (a `while` holds its body's ops): each op is
    charged its self time, its duration less that of the ops inside it;
  * host spans are the `TraceAnnotation`s on the host plane's threads:
    the program's serving spans (`Tracer(profiler=True)`) and the
    harness's own (`window`, `generator`, `result`).

Busy time is the union of a device's operation intervals inside the
traced window, averaged over the devices; the idle gaps are the holes in
that union, each instant of them labelled with the innermost host span
active then, or "none".
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

WINDOW_SPAN = "window"
OPS_LINE = "XLA Ops"
_INSTRUCTION = re.compile(r"^%?([\w.-]+?)(?:\.\d+)?(?: =|$)")
_JIT = re.compile(r"jit\(([\w.-]+)\)")


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                    # averaged over device planes
    n_devices: int
    kernel_s: dict                   # stable op name -> device seconds
    gaps: list                       # (seconds, label) idle pieces

    def idle_by_label(self) -> dict:
        out: dict[str, float] = {}
        for sec, label in self.gaps:
            out[label] = out.get(label, 0.0) + sec
        return out

    def kernel_seconds(self, pattern: str) -> float | None:
        """Device seconds of every op whose stable name matches `pattern`
        (a regular expression); None where no op matches."""
        rx = re.compile(pattern)
        hits = [s for n, s in self.kernel_s.items() if rx.search(n)]
        return sum(hits) if hits else None


def find_xplane(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def stable_name(hlo: str) -> str:
    """`%fusion.12 = f32[8] fusion(...)` -> `fusion`."""
    m = _INSTRUCTION.match(hlo.strip())
    return m.group(1) if m else hlo.split(" ", 1)[0]


def _varint(b, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return out, i


def _fields(b):
    """(field number, value) of a protobuf message's top level; a
    length-delimited value is a memoryview, a varint an int."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(b, i)
        elif kind == 2:
            size, i = _varint(b, i)
            v, i = b[i:i + size], i + size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            v, i = b[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {kind}")
        yield key >> 3, v


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def pallas_names(path: str) -> dict:
    """{op name: kernel function} of the Pallas kernels on the device
    planes, from each op's `tf_op` stat in the event metadata (XSpace
    planes = 1; XPlane name = 2, event_metadata = 4, stat_metadata = 5;
    map entries key = 1, value = 2; XEventMetadata name = 2, stats = 5;
    XStatMetadata id = 1, name = 2; XStat metadata_id = 1, str = 5)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for field, plane in _fields(space):
        if field != 1:
            continue
        name, metas, stat_names = "", [], {}
        for g, v in _fields(plane):
            if g == 2:
                name = _text(v)
            elif g == 4:
                metas.append(v)
            elif g == 5:
                sm = dict(_fields(dict(_fields(v)).get(2, b"")))
                stat_names[sm.get(1)] = _text(sm.get(2, b""))
        if not name.startswith("/device:TPU:"):
            continue
        tf_op_id = {v: k for k, v in stat_names.items()}.get("tf_op")
        for entry in metas:
            op_name, tf_op = "", ""
            for g, v in _fields(dict(_fields(entry)).get(2, b"")):
                if g == 2:
                    op_name = _text(v)
                elif g == 5:
                    stat = dict(_fields(v))
                    if stat.get(1) == tf_op_id and 5 in stat:
                        tf_op = _text(stat[5])
            jits = _JIT.findall(tf_op)
            if "pallas_call" in tf_op and jits:
                out[op_name] = jits[-1]
    return out


def self_times(events) -> list:
    """(stable name, self ns) of nested (start, end, name) intervals."""
    out, stack = [], []   # stack of [end, name, child ns]

    def close(upto):
        while stack and stack[-1][0] <= upto:
            end, name, child, start = stack.pop()
            out.append((name, (end - start) - child))
            if stack:
                stack[-1][2] += end - start

    for start, end, name in sorted(events, key=lambda e: (e[0], -e[1])):
        close(start)
        stack.append([end, name, 0.0, start])
    close(float("inf"))
    return out


def _union(intervals: list) -> list:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


class _Spans:
    """Host spans sorted by start, for lookups by interval."""

    def __init__(self, spans):
        self.spans = sorted(spans, key=lambda x: x[1])
        self.starts = [s for _, s, _ in self.spans]
        self.longest = max((e - s for _, s, e in self.spans), default=0.0)

    def overlapping(self, g0: float, g1: float) -> list:
        lo = bisect.bisect_left(self.starts, g0 - self.longest)
        hi = bisect.bisect_left(self.starts, g1)
        return [(s, e, n) for n, s, e in self.spans[lo:hi] if e > g0]


def _attribute(g0: float, g1: float, spans: _Spans) -> list:
    """(ns, label) pieces of the gap [g0, g1): each instant goes to the
    innermost (shortest) host span active then, or to "none"."""
    inside = spans.overlapping(g0, g1)
    cuts = sorted({g0, g1} | {x for s, e, _ in inside for x in (s, e)
                              if g0 < x < g1})
    out = []
    for a, b in zip(cuts, cuts[1:]):
        active = [(e - s, n) for s, e, n in inside if s <= a and e >= b]
        out.append((b - a, min(active)[1] if active else "none"))
    return out


def reduce_profile(path: str, span_names) -> Reduced:
    """Reduce one xplane file; `span_names` are the host spans to keep."""
    from jax.profiler import ProfileData

    span_names = set(span_names) | {WINDOW_SPAN}
    kernels = pallas_names(path)
    pd = ProfileData.from_file(path)
    spans, devices = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            devices.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in span_names:
                        spans.append((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
    win = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not win:
        raise ValueError(f"{path}: no '{WINDOW_SPAN}' span")
    w0, w1 = min(s for s, _ in win), max(e for _, e in win)
    spans = [x for x in spans if x[0] != WINDOW_SPAN]
    lookup = _Spans(spans)
    kernel_ns: dict[str, float] = {}
    busy_ns, gaps = 0.0, []
    n_dev = 0
    for plane in devices:
        ops = [ln for ln in plane.lines if ln.name == OPS_LINE]
        if not ops:
            continue
        n_dev += 1
        iv = []
        for ev in ops[0].events:
            s, e = ev.start_ns, ev.start_ns + ev.duration_ns
            s, e = max(s, w0), min(e, w1)
            if e > s:
                iv.append((s, e, kernels.get(ev.name)
                           or stable_name(ev.name)))
        for name, ns in self_times(iv):
            kernel_ns[name] = kernel_ns.get(name, 0.0) + ns
        merged = _union([(s, e) for s, e, _ in iv])
        busy_ns += sum(e - s for s, e in merged)
        edges = [w0] + [x for se in merged for x in se] + [w1]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 > g0:
                gaps += [(ns / 1e9, label)
                         for ns, label in _attribute(g0, g1, lookup)]
    n = max(n_dev, 1)
    return Reduced(
        window_s=(w1 - w0) / 1e9, busy_s=busy_ns / n / 1e9, n_devices=n_dev,
        kernel_s={k: v / n / 1e9 for k, v in kernel_ns.items()},
        gaps=gaps,
    )
