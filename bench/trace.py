"""Device trace of a window, and its reduction to what the per-layer
readers need.

``capture`` runs the window under JAX's profiler (Python tracing off,
host spans on). ``reduce`` reads the ``.xplane.pb`` it wrote:

- device planes are those named ``/device:<TPU|GPU>:<n>``; on each, the
  busy time is the union of the intervals of its op events (the
  ``XLA Ops`` line, or every line if there is none), clipped to the
  window;
- device time per program is the summed duration of the events of the
  plane's ``XLA Modules`` line, grouped by program name with the
  trailing ``(<id>)`` removed;
- the window is the benchmark's host span ``bench.window``; idle gaps
  are the stretches of the window in which a device ran nothing, each
  labelled by the innermost benchmark span (``bench.*``) around its
  middle, and by the longest host event in it.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
import shutil

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
PROGRAM_ID = re.compile(r"\(\d+\)$")
WINDOW = "bench.window"


@dataclasses.dataclass
class Reduced:
    """What a trace says about one window."""

    window_ns: tuple[int, int]
    busy_ns: list[int]                       # per device, in the window
    program_ns: dict[str, int]               # summed over devices
    gaps: list[tuple[str, int]]              # (label, ns), longest first

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        """Busy seconds averaged over the devices."""
        return sum(self.busy_ns) / len(self.busy_ns) * 1e-9

    def program_s(self, names) -> float | None:
        """Device seconds of the programs whose name holds any of
        ``names``, summed over devices; None where none ran."""
        hits = [ns for prog, ns in self.program_ns.items()
                if any(n in prog for n in names)]
        return sum(hits) * 1e-9 if hits else None


def capture(log_dir: str, fn):
    """Run ``fn`` under the profiler, writing to ``log_dir`` (emptied
    first); returns ``fn``'s result and the ``.xplane.pb`` path."""
    import jax

    shutil.rmtree(log_dir, ignore_errors=True)
    os.makedirs(log_dir, exist_ok=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return out, max(paths, key=os.path.getmtime)


def _union(intervals) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(intervals, lo: int, hi: int):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def reduce(path: str, n_gaps: int = 10) -> Reduced:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    host_spans, host_events, devices = [], [], []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            devices.append(plane)
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                iv = (ev.name, int(ev.start_ns), int(ev.end_ns))
                (host_spans if ev.name.startswith("bench.")
                 else host_events).append(iv)
    windows = [(s, e) for n, s, e in host_spans if n == WINDOW]
    if not windows or not devices:
        raise ValueError(f"trace {path} holds no {WINDOW!r} span or no "
                         f"device plane")
    lo, hi = windows[0]
    busy, programs, gaps = [], {}, []
    for plane in devices:
        lines = {ln.name: ln for ln in plane.lines}
        op_lines = ([lines["XLA Ops"]] if "XLA Ops" in lines
                    else list(plane.lines))
        ivs = _union(_clip(((int(e.start_ns), int(e.end_ns))
                            for ln in op_lines for e in ln.events), lo, hi))
        busy.append(sum(e - s for s, e in ivs))
        for ev in (lines["XLA Modules"].events if "XLA Modules" in lines
                   else ()):
            if int(ev.end_ns) > lo and int(ev.start_ns) < hi:
                name = PROGRAM_ID.sub("", ev.name)
                programs[name] = programs.get(name, 0) + int(ev.duration_ns)
        edges = [lo] + [x for iv in ivs for x in iv] + [hi]
        gaps += [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
    gaps.sort(key=lambda g: g[0] - g[1])
    labelled = [(_label(s, e, host_spans, host_events), e - s)
                for s, e in gaps[:n_gaps]]
    return Reduced(window_ns=(lo, hi), busy_ns=busy, program_ns=programs,
                   gaps=labelled)


def _label(s: int, e: int, spans, events) -> str:
    """The innermost benchmark span around the gap's middle, and the
    host event that covers most of the gap."""
    mid = (s + e) // 2
    around = [(n, a, b) for n, a, b in spans
              if n != WINDOW and a <= mid <= b]
    span = min(around, key=lambda x: x[2] - x[1])[0] if around else "between"
    best, cover = "", 0
    for n, a, b in events:
        c = min(b, e) - max(a, s)
        if c > cover:
            best, cover = n, c
    return f"{span}:{best}" if best else span
