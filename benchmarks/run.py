"""Benchmark harness — one module per paper table/figure.

Each row: ``name,us_per_call,derived`` CSV. Additionally, every benchmark's
emitted rows (plus whatever dict its ``run()`` returns) are written to a
machine-readable ``BENCH_<slug>.json`` artifact so the perf trajectory is
tracked from PR to PR (``BENCH_OUT_DIR`` overrides the destination). Every
artifact carries an ``env`` stamp (jax version, device platform/kind/count
— see ``benchmarks.common.bench_env``) so baselines from different
toolchains or hardware are distinguishable at a glance.

``--only <slug>[,<slug>...]`` runs a subset by artifact slug — the CI
bench-gate uses ``--only search_perf`` and compares the fresh artifact
against the committed baseline with ``scripts/check_bench.py``.
"""
from __future__ import annotations

import argparse
import os
import sys
import time
import traceback

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_ROOT, "src"))
sys.path.insert(0, _ROOT)  # the `benchmarks` package itself, in script mode


def main() -> None:
    from benchmarks import (bench_bimetric, bench_covertree, bench_model_gap,
                            bench_search_perf, bench_seeding,
                            bench_serve_async, bench_serve_faults,
                            bench_table1, common)
    from repro.launch.compile_cache import use_compile_cache

    benches = [
        ("table1", "table1", bench_table1.run),
        ("fig1", "bimetric", bench_bimetric.run),
        ("fig2", "model_gap", bench_model_gap.run),
        ("fig3", "seeding", bench_seeding.run),
        ("covertree", "covertree", bench_covertree.run),
        ("perf", "search_perf", bench_search_perf.run),
        ("serve_async", "serve_async", bench_serve_async.run),
        ("serve_faults", "serve_faults", bench_serve_faults.run),
    ]
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--only", default=None, metavar="SLUG[,SLUG...]",
                    help="run only the benchmarks with these artifact slugs")
    args = ap.parse_args()
    use_compile_cache()
    if args.only:
        wanted = set(args.only.split(","))
        unknown = wanted - {slug for _, slug, _ in benches}
        if unknown:
            raise SystemExit(f"unknown bench slug(s): {sorted(unknown)}")
        benches = [b for b in benches if b[1] in wanted]
    print("name,us_per_call,derived")
    failures = []
    for name, slug, fn in benches:
        common.drain_emitted()
        t0 = time.time()
        try:
            result = fn()
            wall = time.time() - t0
            if result is None:
                print(f"WARNING: bench {name!r} returned no result dict — "
                      f"BENCH_{slug}.json will carry result: null, so "
                      "nothing in it is gateable by scripts/check_bench.py",
                      file=sys.stderr)
            print(f"{name}/_wall,{wall*1e6:.0f},seconds={wall:.1f}")
            common.write_bench_json(slug, {
                "bench": name,
                "wall_seconds": wall,
                "rows": common.drain_emitted(),
                "result": result,
            })
        except Exception as e:
            traceback.print_exc()
            failures.append((name, repr(e)))
    if failures:
        print("FAILURES:", failures)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
