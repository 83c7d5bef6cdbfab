"""Interval arithmetic over a trace's intervals.

An interval is ``(start_ns, end_ns)``; a list of them may overlap and come
in any order. Every function reads its arguments as the union of their
intervals and returns that union's sorted, disjoint intervals.
"""
from __future__ import annotations


def union(ivs) -> list:
    """The sorted, disjoint intervals covering ``ivs``."""
    out: list = []
    for s, e in sorted(ivs):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        elif e > s:
            out.append((s, e))
    return out


def intersect(a, b) -> list:
    """The parts of ``a`` that ``b`` also covers."""
    a, b = union(a), union(b)
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(a, b) -> list:
    """The parts of ``a`` that ``b`` does not cover."""
    out = []
    b = union(b)
    j = 0
    for s, e in union(a):
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > s:
                out.append((s, b[k][0]))
            s = max(s, b[k][1])
            k += 1
        if e > s:
            out.append((s, e))
    return out


def total_s(ivs) -> float:
    """Seconds the union of ``ivs`` covers."""
    return sum(e - s for s, e in union(ivs)) * 1e-9
