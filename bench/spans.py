"""The program's own host spans in a window's profiler trace, and the
device idle time inside them.

The engine marks its layers with ``repro.*`` profiler spans
(``queueing.run``: ``repro.run`` around each engine call, and inside it
``repro.plan``, ``repro.chunk.input``, ``repro.sample``,
``repro.stage``, ``repro.chunk.slot``, ``repro.chunk.dispatch``,
``repro.drain`` and ``repro.finalize``, each tagged with the call's
id). They are written
into the same trace as the device planes, on the same clock, so each
stretch of a device's idle time can be put down to what the host was
doing. ``trace.reduce`` keeps only the longest idle gaps; ``read`` reads
the whole ``.xplane.pb`` again:

- ``host_spans``: every ``repro.*`` event of the host planes, with its
  host line (one thread) and its metadata;
- each device's idle intervals in the window: the complement of its
  busy union, computed as ``trace.reduce`` computes it, so the idle
  time here sums to ``1 - busy_s / window_s`` of the window.

A per-layer reader finds the trace its run wrote under the checkout's
``.bench_cache/trace`` (``window_of``) and takes the share of the window
in which the devices sat idle inside (or outside) the spans it names.
A trace of a program without these spans reads None.
"""
from __future__ import annotations

import dataclasses
import functools
import glob
import os
from pathlib import Path

from bench import trace
from bench.harness import TRACE_DIR

PREFIX = "repro."
ENGINE = "repro.run"


@dataclasses.dataclass(frozen=True)
class Span:
    """One ``repro.*`` host span."""

    name: str
    start_ns: int
    end_ns: int
    line: tuple[int, int]   # (plane, line) of the trace: one host thread
    meta: dict


def host_spans(data) -> list[Span]:
    """The ``repro.*`` events of the host planes of a
    ``jax.profiler.ProfileData``, in plane, line and event order."""
    return [Span(ev.name, int(ev.start_ns), int(ev.end_ns), (p, li),
                 dict(ev.stats))
            for p, plane in enumerate(data.planes)
            if plane.name.startswith("/host:")
            for li, line in enumerate(plane.lines)
            for ev in line.events if ev.name.startswith(PREFIX)]


def _overlap(a, b) -> int:
    """Nanoseconds shared by two sorted lists of disjoint intervals."""
    i = j = total = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


@dataclasses.dataclass
class Window:
    """What a trace says about the program's spans in one window."""

    window_ns: tuple[int, int]
    spans: list[Span]                      # repro.*, clipped to the window
    idle_ns: list[list[tuple[int, int]]]   # per device, sorted
    bench_spans: list                      # (name, start, end), bench.*
    host_events: list                      # (name, start, end), the rest

    @property
    def length_ns(self) -> int:
        return self.window_ns[1] - self.window_ns[0]

    def has_engine(self) -> bool:
        return any(s.name == ENGINE for s in self.spans)

    def covered(self, names) -> list[tuple[int, int]]:
        return trace._union((s.start_ns, s.end_ns) for s in self.spans
                            if s.name in names)

    def _share(self, intervals) -> float:
        idle = sum(_overlap(ivs, intervals) for ivs in self.idle_ns)
        return idle / len(self.idle_ns) / self.length_ns

    def idle_share(self) -> float:
        """Idle share of the window, averaged over devices."""
        return self._share([self.window_ns])

    def idle_share_in(self, names) -> float:
        """Share of the window the devices sat idle, on average, inside
        any span named in ``names``."""
        return self._share(self.covered(names))

    def idle_share_outside(self, names) -> float:
        """Share of the window the devices sat idle, on average, outside
        every span named in ``names``."""
        lo, hi = self.window_ns
        edges = [lo] + [x for iv in self.covered(names) for x in iv] + [hi]
        return self._share([(s, e) for s, e in zip(edges[::2], edges[1::2])
                            if e > s])

    def label(self, s: int, e: int) -> str:
        """``trace``'s label of an idle gap, with the innermost
        ``repro.*`` span of each host line that holds one at the gap's
        middle: ``bench.query/repro.chunk.input+repro.stage:<event>``."""
        base = trace._label(s, e, self.bench_spans, self.host_events)
        mid = (s + e) // 2
        inner: dict[tuple[int, int], Span] = {}
        for sp in self.spans:
            if sp.start_ns <= mid <= sp.end_ns:
                held = inner.get(sp.line)
                if held is None or (sp.end_ns - sp.start_ns
                                    < held.end_ns - held.start_ns):
                    inner[sp.line] = sp
        if not inner:
            return base
        names = "+".join(inner[k].name for k in sorted(inner))
        head, sep, tail = base.partition(":")
        return f"{head}/{names}{sep}{tail}"

    def gaps(self, n: int = 10) -> list[tuple[str, int]]:
        """The ``n`` longest idle gaps of any device, labelled."""
        longest = sorted((iv for ivs in self.idle_ns for iv in ivs),
                         key=lambda g: g[0] - g[1])[:n]
        return [(self.label(s, e), e - s) for s, e in longest]


def read(path: str) -> Window:
    """The spans, idle intervals and host events of the trace at
    ``path`` (an ``.xplane.pb`` holding a ``bench.window`` span)."""
    return _read(path, os.stat(path).st_mtime_ns)


@functools.lru_cache(maxsize=1)
def _read(path: str, _mtime_ns: int) -> Window:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    bench_spans, host_events, devices = [], [], []
    for plane in data.planes:
        if trace.DEVICE_PLANE.match(plane.name):
            devices.append(plane)
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                iv = (ev.name, int(ev.start_ns), int(ev.end_ns))
                if ev.name.startswith("bench."):
                    bench_spans.append(iv)
                elif not ev.name.startswith(PREFIX):
                    host_events.append(iv)
    windows = [(s, e) for n, s, e in bench_spans if n == trace.WINDOW]
    if not windows or not devices:
        raise ValueError(f"trace {path} holds no {trace.WINDOW!r} span or "
                         f"no device plane")
    lo, hi = windows[0]
    idle = []
    for plane in devices:
        lines = {ln.name: ln for ln in plane.lines}
        op_lines = ([lines["XLA Ops"]] if "XLA Ops" in lines
                    else list(plane.lines))
        busy = trace._union(trace._clip(
            ((int(e.start_ns), int(e.end_ns))
             for ln in op_lines for e in ln.events), lo, hi))
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        idle.append([(s, e) for s, e in zip(edges[::2], edges[1::2])
                     if e > s])
    spans = [dataclasses.replace(sp, start_ns=max(sp.start_ns, lo),
                                 end_ns=min(sp.end_ns, hi))
             for sp in host_spans(data)
             if sp.end_ns > lo and sp.start_ns < hi]
    return Window(window_ns=(lo, hi), spans=spans, idle_ns=idle,
                  bench_spans=bench_spans, host_events=host_events)


def window_of(ctx, root: Path) -> Window | None:
    """The ``Window`` of the trace a ``--trace 1`` run of the checkout at
    ``root`` wrote (the newest under ``.bench_cache/trace``), or None
    where it holds no ``repro.run`` span: a program without the spans.
    A trace of another window than ``ctx.reduced``'s is refused."""
    paths = glob.glob(str(root / TRACE_DIR / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no trace under {root / TRACE_DIR}")
    win = read(max(paths, key=os.path.getmtime))
    if win.window_ns != tuple(ctx.reduced.window_ns):
        raise ValueError(f"the trace's window {win.window_ns} is not the "
                         f"run's {ctx.reduced.window_ns}")
    return win if win.has_engine() else None


# Disjoint parts of the consumer's time: outside every engine call, then
# the engine's own spans in the order one call runs them. Idle time in
# none of them is inside an engine call but between its spans.
PARTS = (("plan", ("repro.plan",)), ("input", ("repro.chunk.input",)),
         ("dispatch", ("repro.chunk.dispatch",)), ("drain", ("repro.drain",)),
         ("finalize", ("repro.finalize",)))
# Producer-side spans: their idle time overlaps the consumer's parts.
PRODUCER = ("repro.sample", "repro.stage", "repro.chunk.slot")


def note_split(ctx, win: Window) -> None:
    """Lines for the run's output: the idle share split into the
    consumer's disjoint parts and the rest; the idle time inside the
    producer's spans; the mean length of each span; the longest gaps
    labelled with the spans around them."""
    idle = win.idle_share()
    shares = {"outside_engine": win.idle_share_outside((ENGINE,))}
    shares.update((k, win.idle_share_in(names)) for k, names in PARTS)
    parts = " ".join(f"{k}={v!r}" for k, v in shares.items())
    ctx.notes.append(f"idle_split idle_share={idle!r} {parts} "
                     f"unattributed={idle - sum(shares.values())!r}")
    ctx.notes.append("idle_producer " + " ".join(
        f"{n}={win.idle_share_in((n,))!r}" for n in PRODUCER))
    by_name: dict[str, list[int]] = {}
    for sp in win.spans:
        by_name.setdefault(sp.name, []).append(sp.end_ns - sp.start_ns)
    ctx.notes.append("span_mean_ms " + " ".join(
        f"{n}={sum(d) / len(d) * 1e-6!r}x{len(d)}"
        for n, d in sorted(by_name.items())))
    for label, ns in win.gaps():
        ctx.notes.append(f"idle_gap s={ns * 1e-9!r} {label}")
