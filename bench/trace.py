"""The profiler trace of a run's window, and its reduction to numbers.

`Tracer` wraps the measured window in `jax.profiler` (``--trace 1`` only)
and marks the benchmark's own host phases with `TraceAnnotation`s named
``bench.window`` and ``bench.<phase>`` (``bench.train.step``). `reduce`
reads the ``.xplane.pb`` file back with
`jax.profiler.ProfileData` and gives, inside the ``bench.window`` span:

* ``busy_s``: the union of the intervals in which an operation ran on the
  device (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane), averaged
  over the chips; ``window_s``: the span's length;
* per-operation device seconds (summed over chips, over the chip count);
* each idle gap of the first chip, named by the innermost benchmark
  annotation running on the host at the gap's midpoint.
"""
from __future__ import annotations

import contextlib
import dataclasses
import pathlib
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
WINDOW = "bench.window"


class Tracer:
    """Profiler around the window when ``active``; no-ops otherwise."""

    def __init__(self, active: bool, log_dir: str | None = None):
        self.active = bool(active)
        self.log_dir = log_dir

    @contextlib.contextmanager
    def window(self):
        import jax

        if not self.active:
            yield
            return
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(WINDOW):
                yield
        finally:
            jax.profiler.stop_trace()

    def annotate(self, phase: str):
        """A host span ``bench.<phase>`` in the trace (nothing when off)."""
        if not self.active:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(f"bench.{phase}")


@dataclasses.dataclass
class Op:
    name: str
    seconds: float


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float
    n_devices: int
    ops: dict[str, Op]                      # by op name, seconds per chip
    idle_gaps: list[tuple[str, float]]      # (host annotation, seconds), longest first

    def op_seconds(self, pattern: str) -> float:
        """Device seconds per chip of the ops whose name matches ``pattern``."""
        rx = re.compile(pattern)
        return sum(op.seconds for name, op in self.ops.items() if rx.search(name))

    def breakdown(self, n: int = 10) -> dict:
        top = sorted(self.ops.values(), key=lambda o: -o.seconds)[:n]
        return {"device_ops": [[short_name(o.name), o.seconds] for o in top],
                "idle_gaps": [[name, s] for name, s in self.idle_gaps[:n]]}


_LAYOUT = re.compile(r"\{[^{}]*\}")
_HLO = re.compile(r"^(%?[\w.\-]+) = (\([^()]*\)|\S+) ([\w\-]+)\(")


def short_name(text: str) -> str:
    """``%name = shape opcode`` of an op's HLO text (layouts dropped), with
    a custom call's target; the text itself where it does not parse."""
    prev = None
    while prev != text:
        prev, text = text, _LAYOUT.sub("", text)
    m = _HLO.match(text)
    if m is None:
        return text[:160]
    out = f"{m.group(1)} = {m.group(2)} {m.group(3)}"
    target = re.search(r'custom_call_target="([^"]+)"', text)
    return f"{out} {target.group(1)}" if target else out


def xplane_file(log_dir: str) -> pathlib.Path:
    files = sorted(pathlib.Path(log_dir).rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(a: int, b: int, lo: int, hi: int) -> tuple[int, int] | None:
    a, b = max(a, lo), min(b, hi)
    return (a, b) if b > a else None


def reduce(path) -> TraceSummary:
    """Reduce one ``.xplane.pb`` file (a path or a directory holding one)."""
    from jax.profiler import ProfileData

    path = pathlib.Path(path)
    if path.is_dir():
        path = xplane_file(str(path))
    pd = ProfileData.from_file(str(path))
    host: list[tuple[str, int, int]] = []
    devices = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            devices.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        s = int(ev.start_ns)
                        host.append((ev.name, s, s + int(ev.duration_ns)))
    windows = [(a, b) for name, a, b in host if name == WINDOW]
    if not windows:
        raise ValueError(f"{path}: no {WINDOW} annotation on the host")
    if not devices:
        raise ValueError(f"{path}: no device plane")
    lo, hi = windows[0]
    ops: dict[str, Op] = {}
    busy = 0.0
    gaps: list[tuple[int, int]] = []
    for k, plane in enumerate(devices):
        ivs = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                s = int(ev.start_ns)
                c = _clip(s, s + int(ev.duration_ns), lo, hi)
                if c is None:
                    continue
                ivs.append(c)
                op = ops.get(ev.name)
                if op is None:
                    op = ops[ev.name] = Op(ev.name, 0.0)
                op.seconds += (c[1] - c[0]) / 1e9 / len(devices)
        merged = _union(ivs)
        busy += sum(b - a for a, b in merged) / 1e9 / len(devices)
        if k == 0:
            edges = [lo] + [x for iv in merged for x in iv] + [hi]
            gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i]]
    phases = [(name, a, b) for name, a, b in host if name != WINDOW]
    named = []
    for a, b in gaps:
        mid = (a + b) // 2
        inner = [(pb - pa, name) for name, pa, pb in phases if pa <= mid < pb]
        named.append((min(inner)[1] if inner else "host.other", (b - a) / 1e9))
    named.sort(key=lambda g: -g[1])
    return TraceSummary(window_s=(hi - lo) / 1e9, busy_s=busy, n_devices=len(devices),
                        ops=ops, idle_gaps=named)
