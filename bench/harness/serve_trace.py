"""The engine's own ``serve.*`` spans in a traced run, beside the device's
idle intervals.

:func:`harness.trace.reduce` keeps the window's busy time, its programs
and its longest idle gaps. The readers of ``admit_idle_share``,
``wave_idle_share`` and ``stage1_ms`` need the intervals themselves: when
no chip ran an operation, and when each of the engine's spans was open.
:func:`for_ctx` finds the traced run's own ``.xplane.pb`` (the newest
under the checkout's ``.bench_trace/`` whose ``bench.window`` span is the
one ``ctx["trace"]`` reduced), reads it once and logs what the spans show.
A run without a trace, or a program without the spans, gives ``None``, and
the readers leave their metric out of the line.
"""
from __future__ import annotations

import dataclasses
import os
import sys
from pathlib import Path

from harness import spans as intervals
from harness import trace

SERVE_PREFIX = "serve."
TRACE_ROOT = Path(__file__).resolve().parents[2] / ".bench_trace"


@dataclasses.dataclass
class ServeTrace:
    window: tuple  # (start_ns, end_ns) of the bench.window span
    idle: list  # [(start_ns, end_ns)] of the window with no chip busy
    # serve.* span name -> [(start_ns, end_ns, line)] of the spans that
    # overlap the window, unclipped; ``line`` numbers the host line, one
    # per thread
    spans: dict

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def inside(self, name: str) -> list:
        """``name``'s spans clipped to the window."""
        w0, w1 = self.window
        return [(max(s, w0), min(e, w1)) for s, e, _ in self.spans.get(name, ())]

    def closed(self, name: str) -> list:
        """``name``'s spans that closed inside the window, whole."""
        w0, w1 = self.window
        return [(s, e) for s, e, _ in self.spans.get(name, ()) if w0 < e <= w1]


def reduce(xplane_path: str) -> ServeTrace | None:
    """The window, its idle intervals and the ``serve.*`` spans of one
    trace; ``None`` without a window span or a TPU plane."""
    from jax.profiler import ProfileData

    profile = ProfileData.from_file(str(xplane_path))
    window = None
    serve: dict = {}
    line_no = 0
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == trace.WINDOW_SPAN and window is None:
                    window = (ev.start_ns, ev.end_ns)
                elif ev.name.startswith(SERVE_PREFIX):
                    serve.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.end_ns, line_no))
            line_no += 1
    chips = [p for p in profile.planes if p.name.startswith("/device:TPU:")]
    if window is None or not chips:
        return None
    w0, w1 = window
    busy = [(max(ev.start_ns, w0), min(ev.end_ns, w1))
            for plane in chips for line in plane.lines
            if line.name == "XLA Ops" for ev in line.events]
    in_window = {name: [ev for ev in evs if ev[1] > w0 and ev[0] < w1]
                 for name, evs in serve.items()}
    return ServeTrace(window=window,
                      idle=intervals.subtract([window], busy),
                      spans={k: v for k, v in in_window.items() if v})


def describe(st: ServeTrace, top: int = 10) -> list[str]:
    """Each span's count, host lines, seconds in the window and the
    device's idle seconds inside; then, for the longest idle intervals,
    the spans open at their middle on each host line."""
    lines = []
    for name in sorted(st.spans):
        evs = st.spans[name]
        ivs = st.inside(name)
        lines.append(
            f"span {name}: {len(evs)} in the window, host lines "
            f"{sorted({ln for *_, ln in evs})}; "
            f"{intervals.total_s(ivs):.4f} s, device idle inside "
            f"{intervals.total_s(intervals.intersect(st.idle, ivs)):.4f} s")
    for s, e in sorted(st.idle, key=lambda iv: iv[0] - iv[1])[:top]:
        mid = (s + e) / 2
        by_line: dict = {}
        for ln, _, name in sorted((ln, s1, name)
                                  for name, evs in st.spans.items()
                                  for s1, e1, ln in evs if s1 <= mid <= e1):
            by_line.setdefault(ln, []).append(name)
        where = "; ".join(f"line {ln}: {' > '.join(names)}"
                          for ln, names in by_line.items())
        lines.append(f"idle {(e - s) * 1e-9:.4f} s: {where or 'no serve span'}")
    return lines


_read: dict = {}  # (path, mtime) -> ServeTrace | None


def for_ctx(ctx: dict) -> ServeTrace | None:
    """The ``serve.*`` spans of the trace that ``ctx["trace"]`` reduced."""
    tr = ctx.get("trace")
    if tr is None:
        return None
    paths = sorted(TRACE_ROOT.glob("**/*.xplane.pb"), key=os.path.getmtime,
                   reverse=True)
    for path in paths:
        key = (str(path), os.path.getmtime(path))
        if key not in _read:
            st = _read[key] = reduce(path)
            if st is not None and st.window_s == tr.window_s and st.spans:
                print("\n".join(describe(st)), file=sys.stderr, flush=True)
        st = _read[key]
        if st is not None and st.window_s == tr.window_s:
            return st if st.spans else None
    return None
