"""Readings that the correctness limits are set from.

    python3 bench/control.py --workload <name> --seconds <s> --seeds <n> ...

For each seed, one run of the cell as ``bench/run.py`` makes it (set-up,
a window of ``--seconds`` at the cell's own load, the check), which also
reads the controls: the plain reference put in the program's place in the
precision below the one each tower is served in, over the same queries and
documents: float8 e4m3 for the expensive tower (``control_dist_gap``,
``control_nn_miss``), bfloat16 and float8 for the cheap one
(``control_cheap_gap_bf16``, ``control_cheap_gap_fp8``). One JSON line
per seed, then a summary: the program's largest reading of each number
(the lower readings) and each control's smallest (the upper). Needs a TPU,
like ``bench/run.py``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

from run import ROOT, setup_jax  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    jax, _ = setup_jax()
    from harness.cell import log, run_cell
    from harness.spec import Spec

    spec = Spec(ROOT)
    if jax.devices()[0].platform != "tpu":
        log("control: needs a TPU")
        return 2
    rows = []
    for seed in args.seeds:
        out = run_cell(spec, args.workload, seed, args.seconds, False,
                       t_start=time.perf_counter(), trace_dir=ROOT,
                       control=True)
        row = {"seed": seed, "correct": out["correct"],
               "attempted": out["attempted"], "failed": out["failed"],
               **out["control"], **out["readings"]}
        rows.append(row)
        print(json.dumps(row), flush=True)
    summary = {"workload": args.workload, "seeds": len(rows)}
    for k in rows[0]:
        if k.startswith("control_"):
            summary[f"{k}_min"] = min(r[k] for r in rows)
        elif k in ("dist_gap", "nn_miss", "cheap_gap"):
            summary[f"program_{k}_max"] = max(r[k] for r in rows)
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
