"""Record ``data/serve_fixture.xplane.pb``: the engine's span pattern with
known idle, for the readers of ``admit_idle_share``, ``wave_idle_share``
and ``stage1_ms``. Needs a TPU (the trace must hold its device plane):

    python3 bench/tests/record_serve_fixture.py <out_dir>

Inside a ``bench.window`` span, a drive thread runs three rounds through
the engine's own span helper (``BiMetricEngine._span``), a tower thread
serving its drains and query embeds:

* ``serve.admit``: ``serve.cheap_embed`` (a small program),
  ``serve.stage1`` (a 4 ms host sleep, the device idle, then a small
  program) and ``serve.query_wait`` (the tower thread's matmuls);
* ``serve.wave``: ``serve.plan`` (a small program), the drain's matmuls
  on the tower thread while the drive thread stages the next group in a
  ``serve.admit`` of a 3 ms sleep (the device busy), ``serve.drain_wait``,
  ``serve.gather`` (a 2 ms host sleep, the device idle) and
  ``serve.commit`` (a small program);
* ``serve.resolve``: a 1 ms host sleep.

The engine's span counters at the window's edges go to
``serve_fixture.counters.json`` beside the trace.
"""
from __future__ import annotations

import concurrent.futures
import dataclasses
import json
import shutil
import sys
import threading
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from harness import serve_trace, trace  # noqa: E402
from repro.serve.engine import BiMetricEngine, EngineCounters  # noqa: E402


class _Spans:
    """Just what ``BiMetricEngine._span`` reads of an engine."""

    _span = BiMetricEngine._span

    def __init__(self):
        self._mu = threading.RLock()
        self._counters = EngineCounters()

    def snapshot(self) -> dict:
        with self._mu:
            return {"span_n": dict(self._counters.span_n),
                    "span_s": dict(self._counters.span_s)}


@jax.jit
def _tower(x):
    def body(_, y):
        return (y @ x).astype(jnp.bfloat16)
    return jax.lax.fori_loop(0, 10, body, x)


@jax.jit
def _small(x):
    return jnp.tanh(x) * 2.0


def main(out_dir: str) -> None:
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("needs a TPU: the fixture is a chip trace")
    out = Path(out_dir)
    big = jnp.ones((4096, 4096), jnp.bfloat16) * 1e-3
    small = jnp.ones((256, 256), jnp.float32)
    _tower(big).block_until_ready()  # compile outside the trace
    _small(small).block_until_ready()
    eng = _Spans()
    lane = concurrent.futures.ThreadPoolExecutor(
        1, thread_name_prefix="serve-tower")

    def tower(name, **ids):
        def run():
            with eng._span(name, **ids):
                return _tower(big).block_until_ready()
        return lane.submit(run)

    snaps = {}

    def drive():
        wave = 0
        for group in range(1, 4):
            with eng._span("serve.admit", group=group, requests=8):
                qfut = tower("serve.tower.query_embed", group=group)
                with eng._span("serve.cheap_embed", group=group):
                    _small(small).block_until_ready()
                with eng._span("serve.stage1", group=group):
                    time.sleep(0.004)
                    _small(small).block_until_ready()
                with eng._span("serve.query_wait", group=group):
                    qfut.result()
            wave += 1
            with eng._span("serve.wave", wave=wave, entry=1):
                with eng._span("serve.plan", wave=wave):
                    _small(small).block_until_ready()
                dfut = tower("serve.tower.drain", wave=wave, rows=64)
                with eng._span("serve.admit", group=10 + group, requests=0):
                    time.sleep(0.003)
                with eng._span("serve.drain_wait", wave=wave):
                    dfut.result()
                with eng._span("serve.gather", wave=wave):
                    time.sleep(0.002)
                with eng._span("serve.commit", wave=wave):
                    _small(small).block_until_ready()
            with eng._span("serve.resolve", resolved=8):
                time.sleep(0.001)

    tmp = out / "trace"
    shutil.rmtree(tmp, ignore_errors=True)
    with trace.capture(str(tmp)):
        with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
            snaps["open"] = eng.snapshot()
            t = threading.Thread(target=drive, name="serve-drive")
            t.start()
            t.join()
            snaps["close"] = eng.snapshot()
    lane.shutdown()
    out.mkdir(parents=True, exist_ok=True)
    shutil.copy(trace.find_xplane(str(tmp)), out / "serve_fixture.xplane.pb")
    (out / "serve_fixture.counters.json").write_text(
        json.dumps(snaps, indent=1) + "\n")
    r = trace.reduce(str(out / "serve_fixture.xplane.pb"))
    st = serve_trace.reduce(out / "serve_fixture.xplane.pb")
    print(json.dumps({"window_s": r.window_s, "busy_s": r.busy_s,
                      "spans": {k: len(v) for k, v in st.spans.items()},
                      "idle": len(st.idle),
                      "counters": dataclasses.asdict(EngineCounters(
                          **snaps["close"]))["span_n"]}))


if __name__ == "__main__":
    main(sys.argv[1])
