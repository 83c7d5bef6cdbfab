"""Run one cell of the chip benchmark once.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

``<name>`` is a ``workloads`` entry of ``BENCHMARK.json``; the cell's
configuration, traffic mix and metrics are found by name under ``bench/``.
The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number the correctness comparison
read, beside its limit. The same numbers end standard error.

The run needs a TPU with as many chips as the cell asks for: it exits
non-zero and prints no result anywhere else. JAX's persistent compilation
cache lives in the checkout at ``.jax_cache``, so only a checkout's first
run of a cell compiles.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


CACHE_MAX_BYTES = 2 << 30


def setup_jax():
    """Point JAX's persistent compilation cache into the checkout (a fixed
    path) and cache every program, however short its compile.

    The cache is bounded at ``CACHE_MAX_BYTES`` (least recently used out
    first). The program bakes the corpus into some of its executables
    (the index build's and stage 1's), so every seed writes new ones; a
    bound of a few hundred MiB lets them push out the programs every seed
    shares, and each run would compile those again."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(BENCH), str(ROOT / "src")]
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_compilation_cache_max_size", CACHE_MAX_BYTES)
    from repro.launch.compile_cache import use_compile_cache

    return jax, use_compile_cache()


def main(argv=None) -> int:
    args = parse(argv)
    jax, cache_dir = setup_jax()
    from harness.cell import log, run_cell
    from harness.spec import Spec

    spec = Spec(ROOT)
    cell = spec.cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        log(f"bench: cell {args.workload} needs {cell['chips']} TPU chip(s); "
            f"JAX found {len(devices)} {devices[0].platform} device(s)")
        return 2
    log(f"compile cache: {cache_dir}")
    out = run_cell(spec, args.workload, args.seed, args.seconds,
                   bool(args.trace), t_start=T_START,
                   trace_dir=ROOT / ".bench_trace" / args.workload)
    cache_bytes = sum(p.stat().st_size for p in Path(cache_dir).glob("*"))
    log(f"compile cache holds {cache_bytes} bytes")
    for name, c in out["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
