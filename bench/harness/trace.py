"""Capture a profiler trace of the window and reduce it to numbers.

The reduction reads ``jax.profiler.ProfileData``. On each device plane
(``/device:TPU:<n>``) the ``XLA Ops`` line holds every operation's
interval (the body of a loop is listed inside the loop's own event) and
the ``XLA Modules`` line each program's. The host plane holds the
benchmark's spans (``jax.profiler.TraceAnnotation``) and JAX's own
dispatch spans. The window is the benchmark's ``bench.window`` span.
From those:

* ``busy_s``: the union of the device's operation intervals inside the
  window, averaged over the chips;
* ``module_s``: device seconds per program (``XLA Modules``) in the window;
* ``idle_gaps``: the longest gaps between busy intervals, each named by
  what the host was doing at its middle: the benchmark's tower spans
  first, then the innermost of the host's other spans on a Python thread
  (JAX's dispatch and compile spans among them), then ``bench.submit``
  or ``bench.wait``, else ``"no span"``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import os

import jax
import numpy as np

WINDOW_SPAN = "bench.window"
TOWER_SPANS = ("expensive.embed", "cheap.embed")
CLIENT_SPANS = ("bench.submit", "bench.wait")


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float
    module_s: dict  # program name -> device seconds in the window
    idle_gaps: list  # [(name, seconds)], longest first
    n_chips: int


@contextlib.contextmanager
def capture(log_dir: str):
    """Trace the enclosed block into ``log_dir`` (no Python tracer)."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _merge(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _host_spans(profile):
    """(bench spans by name, other host spans on Python threads)."""
    bench: dict = {}
    other = []
    named = {WINDOW_SPAN, *TOWER_SPANS, *CLIENT_SPANS}
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            python = line.name.startswith("python")
            for ev in line.events:
                if ev.name in named:
                    bench.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.end_ns))
                elif python:
                    other.append((ev.start_ns, ev.end_ns, ev.name))
    return bench, other


def _name_gap(mid, bench, other_arr, other_names):
    for name in TOWER_SPANS:
        if any(s <= mid <= e for s, e in bench.get(name, ())):
            return name
    starts, ends = other_arr
    hit = np.nonzero((starts <= mid) & (ends >= mid))[0]
    if len(hit):
        return other_names[hit[np.argmin(ends[hit] - starts[hit])]]
    for name in CLIENT_SPANS:
        if any(s <= mid <= e for s, e in bench.get(name, ())):
            return name
    return "no span"


def reduce(xplane_path: str, *, top: int = 10) -> Reduced:
    from jax.profiler import ProfileData

    profile = ProfileData.from_file(xplane_path)
    bench, other = _host_spans(profile)
    if not bench.get(WINDOW_SPAN):
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    w0, w1 = bench[WINDOW_SPAN][0]
    chips = [p for p in profile.planes if p.name.startswith("/device:TPU:")]
    if not chips:
        raise ValueError("the trace holds no TPU device plane")
    busy = 0.0
    module_s: dict = {}
    gaps = []
    for plane in chips:
        ivs = []
        for line in plane.lines:
            if line.name not in ("XLA Ops", "XLA Modules"):
                continue
            for ev in line.events:
                s, e = max(ev.start_ns, w0), min(ev.end_ns, w1)
                if e <= s:
                    continue
                if line.name == "XLA Modules":
                    module_s[ev.name] = module_s.get(ev.name, 0.0) \
                        + (e - s) * 1e-9
                    continue
                ivs.append((s, e))
        merged = _merge(ivs)
        busy += sum(e - s for s, e in merged) * 1e-9
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps += [(g0, g1) for g0, g1 in zip(edges[::2], edges[1::2])
                 if g1 > g0]
    gaps.sort(key=lambda g: g[0] - g[1])
    other_arr = (np.array([s for s, _, _ in other], np.float64),
                 np.array([e for _, e, _ in other], np.float64))
    other_names = [n for _, _, n in other]
    named = [(_name_gap((g0 + g1) / 2, bench, other_arr, other_names),
              (g1 - g0) * 1e-9) for g0, g1 in gaps[:top]]
    return Reduced(window_s=(w1 - w0) * 1e-9, busy_s=busy / len(chips),
                   module_s=module_s,
                   idle_gaps=named, n_chips=len(chips))


def breakdown(r: Reduced, top: int = 10) -> dict:
    """The programs that took most device time, and the longest gaps."""
    mods = sorted(r.module_s.items(), key=lambda x: -x[1])[:top]
    return {"device_ops": [[n, s] for n, s in mods],
            "idle_gaps": [[n, s] for n, s in r.idle_gaps[:top]]}
