"""Requests served per second of the window, counting all of the window's
work: each request counts by the share of its service (from its admission
to a slot, ``ServeStats.compute_ms`` before it resolved, until it
resolved) that lies inside the window, so requests in service as the
window opens or closes count by the part of them it holds."""


def served_in(start: float, end: float, t0: float, t1: float) -> float:
    """The share of the service [start, end] inside the window [t0, t1]."""
    if end <= start:
        return float(t0 <= end <= t1)
    return max(0.0, min(end, t1) - max(start, t0)) / (end - start)


def read(ctx):
    n = sum(served_in(r.done - r.result.stats.compute_ms * 1e-3, r.done,
                      ctx["t0"], ctx["t_end"])
            for r in ctx["records"] if r.result is not None)
    return n / ctx["window_s"]
