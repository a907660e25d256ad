"""The program's own names in a profiler trace: device scopes, host spans.

`bench.trace.reduce` reads a trace through `jax.profiler.ProfileData`,
which gives each device op's HLO text and each host event's name, but not
the stats of an op's event metadata. Two of them carry the program's names:
``tf_op``, the JAX name stack of the op (``jit(step)/jvp(quant.calibrate)/
top_k``, with every ``jax.named_scope`` the program put around it) and
``source``, the line that made it. `op_stats` reads them from the
``.xplane.pb`` wire format itself (no protobuf dependency; the event lines,
the bulk of the file, are skipped).

On the host the program's spans (`repro.obs.trace`, each a profiler
annotation) have names of the form ``layer.operation``: lowercase words and
dots, no spaces or parentheses. No runtime event has such a name; the
benchmark's own ``bench.*`` annotations are kept apart.

`read` reduces one trace to:

* ``scope_seconds(scope)``: device seconds per chip of the ops whose
  ``tf_op`` has ``scope`` as a path component, also inside a transform
  wrapper such as ``jvp(...)`` or ``transpose(...)``; a fusion counts by its
  own ``tf_op``, which XLA takes from the fusion's root;
* ``span_idle``: the first chip's idle seconds inside the window, by the
  innermost program span running on the host at each idle instant;
* ``idle_gaps``: `bench.trace.reduce`'s gaps, each named by the innermost
  host span of either kind, ``bench.*`` or the program's, at its midpoint.
"""
from __future__ import annotations

import bisect
import dataclasses
import pathlib
import re

from bench import trace as tr

PROGRAM_SPAN = re.compile(r"^(?!bench\.)[a-z][a-z0-9_]*(?:\.[a-z0-9_]+)+$")
STATS = ("tf_op", "source")

# Field numbers of tsl's xplane.proto: XSpace.planes; XPlane.name,
# .event_metadata, .stat_metadata (maps: an entry's value is field 2);
# XEventMetadata.name, .stats; XStat.metadata_id, .str_value, .ref_value;
# XStatMetadata.id, .name.
_PLANES, _PLANE_NAME, _EVENT_META, _STAT_META = 1, 2, 4, 5
_VALUE = 2
_META_NAME, _META_STATS = 2, 5
_STAT_ID, _STAT_STR, _STAT_REF = 1, 5, 7
_STAT_META_ID, _STAT_META_NAME = 1, 2


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    x = shift = 0
    while True:
        c = buf[i]
        i += 1
        x |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return x, i


def _fields(buf: bytes, lo: int, hi: int):
    """``(field, value)`` of one message in ``buf[lo:hi]``: an int for a
    varint, ``(start, end)`` offsets for a length-delimited field."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wire in (1, 5):
            v, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"unsupported protobuf wire type {wire} at byte {i}")
        yield key >> 3, v


def _text(buf: bytes, span: tuple[int, int]) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _map_values(buf: bytes, entries: list):
    for lo, hi in entries:
        for f, v in _fields(buf, lo, hi):
            if f == _VALUE:
                yield v


def _plane_stats(buf: bytes, event_meta: list, stat_meta: list) -> dict[str, dict[str, str]]:
    names: dict[int, str] = {}
    for lo, hi in _map_values(buf, stat_meta):
        sid, name = None, None
        for f, v in _fields(buf, lo, hi):
            if f == _STAT_META_ID:
                sid = v
            elif f == _STAT_META_NAME:
                name = _text(buf, v)
        names[sid] = name
    out: dict[str, dict[str, str]] = {}
    for lo, hi in _map_values(buf, event_meta):
        op, stats = None, {}
        for f, v in _fields(buf, lo, hi):
            if f == _META_NAME:
                op = _text(buf, v)
            elif f == _META_STATS:
                key, value = None, None
                for g, w in _fields(buf, *v):
                    if g == _STAT_ID:
                        key = names.get(w)
                    elif g == _STAT_STR:
                        value = _text(buf, w)
                    elif g == _STAT_REF:
                        value = names.get(w)
                if key in STATS and value is not None:
                    stats[key] = value
        if op and stats:
            out[op] = stats
    return out


def op_stats(path) -> dict[str, dict[str, str]]:
    """Per device op (its HLO text, the name `bench.trace.reduce` keys ops
    by), its ``tf_op`` (the op type after ``:`` dropped) and ``source``,
    over every ``/device:TPU:<n>`` plane."""
    buf = pathlib.Path(path).read_bytes()
    out: dict[str, dict[str, str]] = {}
    for f, plane in _fields(buf, 0, len(buf)):
        if f != _PLANES:
            continue
        name, event_meta, stat_meta = "", [], []
        for g, v in _fields(buf, *plane):
            if g == _PLANE_NAME:
                name = _text(buf, v)
            elif g == _EVENT_META:
                event_meta.append(v)
            elif g == _STAT_META:
                stat_meta.append(v)
        if tr.DEVICE_PLANE.match(name):
            out.update(_plane_stats(buf, event_meta, stat_meta))
    for stats in out.values():
        if "tf_op" in stats:
            stats["tf_op"] = stats["tf_op"].rpartition(":")[0] or stats["tf_op"]
    return out


def in_scope(tf_op: str, scope: str) -> bool:
    """``scope`` is a component of the name stack ``tf_op``, bare or inside
    a transform wrapper: ``jit(step)/jvp(quant.calibrate)/top_k``."""
    return scope in re.split(r"[/()]", tf_op)


def innermost_at(points: list[int], spans: list[tuple[str, int, int]]) -> list[str | None]:
    """The innermost (shortest, then first by name) of ``spans`` open at
    each of ``points``, or None: one pass over both in time order."""
    order = sorted(range(len(points)), key=points.__getitem__)
    starts = sorted(spans, key=lambda s: s[1])
    open_: list[tuple[int, str, int]] = []
    out: list[str | None] = [None] * len(points)
    j = 0
    for i in order:
        t = points[i]
        while j < len(starts) and starts[j][1] <= t:
            name, a, b = starts[j]
            open_.append((b - a, name, b))
            j += 1
        open_ = [x for x in open_ if x[2] > t]
        out[i] = min(open_)[1] if open_ else None
    return out


def attribute(gaps: list[tuple[int, int]], spans: list[tuple[str, int, int]]) -> dict[str, float]:
    """Seconds of ``gaps`` (ns intervals) by the innermost of ``spans`` open
    at each instant; instants under no span count nowhere."""
    cuts = sorted({t for _, a, b in spans for t in (a, b)})
    pieces = []
    for a, b in gaps:
        edges = [a, *cuts[bisect.bisect_right(cuts, a):bisect.bisect_left(cuts, b)], b]
        pieces += zip(edges, edges[1:])
    out: dict[str, float] = {}
    for name, (a, b) in zip(innermost_at([a for a, _ in pieces], spans), pieces):
        if name is not None:
            out[name] = out.get(name, 0.0) + (b - a) / 1e9
    return out


@dataclasses.dataclass
class Spans:
    summary: tr.TraceSummary           # `bench.trace.reduce` of the same file
    ops: dict[str, dict[str, str]]     # `op_stats`
    spans: list[tuple[str, int, int]]  # the program's host spans, ns
    idle_s: float                      # the first chip's idle time in the window
    span_idle: dict[str, float]
    idle_gaps: list[tuple[str, float]]

    def scope_seconds(self, scope: str) -> float:
        return sum(op.seconds for name, op in self.summary.ops.items()
                   if in_scope(self.ops.get(name, {}).get("tf_op", ""), scope))

    def span_counts(self) -> dict[str, int]:
        counts: dict[str, int] = {}
        for name, _, _ in self.spans:
            counts[name] = counts.get(name, 0) + 1
        return counts


def read(path) -> Spans:
    """Reduce one ``.xplane.pb`` file (a path or a directory holding one)."""
    from jax.profiler import ProfileData

    path = pathlib.Path(path)
    if path.is_dir():
        path = tr.xplane_file(str(path))
    summary = tr.reduce(path)
    pd = ProfileData.from_file(str(path))
    bench, program, device = [], [], None
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    s = int(ev.start_ns)
                    span = (ev.name, s, s + int(ev.duration_ns))
                    if ev.name.startswith("bench."):
                        bench.append(span)
                    elif PROGRAM_SPAN.match(ev.name):
                        program.append(span)
        elif device is None and tr.DEVICE_PLANE.match(plane.name):
            device = plane
    lo, hi = next((a, b) for name, a, b in bench if name == tr.WINDOW)
    ivs = [c for line in device.lines if line.name == tr.OPS_LINE for ev in line.events
           if (c := tr._clip(int(ev.start_ns), int(ev.start_ns) + int(ev.duration_ns), lo, hi))]
    edges = [lo] + [x for iv in tr._union(ivs) for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
    phases = [s for s in bench if s[0] != tr.WINDOW] + program
    names = innermost_at([(a + b) // 2 for a, b in gaps], phases)
    named = sorted(((name or "host.other", (b - a) / 1e9) for name, (a, b) in zip(names, gaps)),
                   key=lambda g: -g[1])
    return Spans(summary=summary, ops=op_stats(path), spans=program,
                 idle_s=sum(b - a for a, b in gaps) / 1e9,
                 span_idle=attribute(gaps, program), idle_gaps=named)

