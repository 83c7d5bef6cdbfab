"""Operations of one tower call, computed from its shapes.

``row_flops(t, seq)`` counts the useful operations (a multiply-add as 2)
that embedding one row of ``seq`` tokens needs, as the tower's own module
counts them (``t["tower"]``, the dense tower where ``t`` names none; see
``harness.model``). Each module states what it counts and leaves out.
"""
from __future__ import annotations

from harness import model


def row_flops(t: dict, seq: int) -> float:
    return float(model.tower_module(t).row_flops(t, seq))
