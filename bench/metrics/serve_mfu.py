"""Useful tower operations over the window, as a share of the chip's bf16
peak: rows that held a query or a document (not padding, not an empty
slot), for both towers, times the operations of one row
(``harness.flops.row_flops``)."""
from harness import flops, peaks


def read(ctx):
    ops = sum(ctx["towers"][k].useful * flops.row_flops(*ctx["tower_sizes"][k])
              for k in ("cheap", "expensive"))
    if not ops:
        return None
    peak = peaks.peaks(ctx["device_kind"])["bf16_flops_per_s"]
    return 100.0 * ops / (ctx["window_s"] * peak)
