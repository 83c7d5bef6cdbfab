"""One run of one cell: set-up, the measured window, the check.

Set-up makes the weights and the data from the seed, builds the engine
(``BiMetricEngine`` indexes the corpus with the cheap tower only) and
serves the mix's warm-up requests, which compiles every program the window
uses. The window then drives ``engine.submit(SearchRequest)`` →
``ServeFuture.result()`` for ``seconds``. Afterwards every request sent is
waited for, the device's memory peak is read, the engine is closed and
freed, and the plain reference checks a sample of the answers.

A traffic mix is ``bench/traffic/<name>.json``, which :func:`drive` reads:

* ``loop``: ``"batch"``, the one kind there is: a caller that sends
  ``batch`` requests together and waits for all of their answers before
  it sends the next, starting ``ramp_s`` seconds before the window opens;
* ``max_requests``: distinct queries made for the run (a run that uses
  them all is refused);
* ``quota``, ``k``: the request's expensive-call budget and result size;
* ``warmup_requests``: requests served before the caller starts, drawn
  apart from the window's, to compile and warm the programs it uses;
* ``check_sample``: how many finished requests the correctness comparison
  draws (from the seed).
"""
from __future__ import annotations

import dataclasses
import gc
import shutil
import sys
import threading
import time
from pathlib import Path

import jax

from harness import check, data as data_mod, model, towers, trace

RESULT_TIMEOUT_S = 300.0  # per request


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


@dataclasses.dataclass
class Record:
    """One request a client sent."""

    index: int
    sent: float  # perf_counter just before submit
    done: float | None = None  # perf_counter when its result was back
    result: object = None
    error: BaseException | None = None


class CompileCounter:
    """Counts traces, backend compiles (by program) and persistent-cache
    hits and misses while ``on`` is set."""

    def __init__(self):
        self.on = False
        self.traces = 0
        self.compiles: dict = {}
        self.cache = {"hits": 0, "misses": 0}
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **kw) -> None:
        if not self.on:
            return
        if event == "/jax/core/compile/jaxpr_trace_duration":
            self.traces += 1
        elif event == "/jax/core/compile/backend_compile_duration":
            name = kw.get("fun_name", "?")
            self.compiles[name] = self.compiles.get(name, 0) + 1

    def _event(self, event: str, **_kw) -> None:
        if self.on and event.startswith("/jax/compilation_cache/cache_"):
            key = event.rsplit("_", 1)[-1]
            if key in self.cache:
                self.cache[key] += 1

    def take(self) -> str:
        """What was counted since the last ``take``, as one line."""
        n = sum(self.compiles.values())
        top = sorted(self.compiles.items(), key=lambda kv: -kv[1])[:5]
        by = ", ".join(f"{k} x{v}" for k, v in top)
        line = (f"{n} backend compiles ({by or 'none'}), {self.traces} "
                f"traces; persistent cache {self.cache['hits']} hits, "
                f"{self.cache['misses']} misses")
        self.traces, self.compiles = 0, {}
        self.cache = {"hits": 0, "misses": 0}
        return line


@dataclasses.dataclass
class Window:
    """What the measured window produced."""

    t0: float
    t_end: float
    records: list  # [Record], by index
    ok: list  # records that resolved with a result
    n_failed: int


def _batch_loop(eng, reqs, batch: int, seconds: float, ramp_s: float,
                at_open) -> tuple:
    """One caller that sends ``batch`` requests together and waits for
    all of their answers before it sends the next ``batch``, from
    ``ramp_s`` seconds before the window opens until it closes.

    The engine admits whatever is queued whenever a slot is free, so
    requests sent one by one, or a queue refilled as single answers come
    back, are admitted in groups whose sizes follow the order in which
    answers come back; that order turns on the seed's data, and every
    admission costs the whole pool a stage-1 pass. With 16 callers that
    each sent again on their own answer, the seeds of one TPU v5e run
    split into those admitted 8 at a time and those admitted 3 to 4 at a
    time, and ``qps`` read 0.85 to 1.19 req/s. A batch of the slot count
    sent together, with the interpreter's thread switch held off while it
    is queued, is admitted as one group in every seed."""
    records: list[Record] = []
    nxt = [0]
    t_start = time.perf_counter()
    t0 = t_start + ramp_s
    t_end = t0 + seconds

    def stamp(rec):
        def done(_fut):
            rec.done = time.perf_counter()
        return done

    def caller():
        while time.perf_counter() < t_end and nxt[0] + batch <= len(reqs):
            sent = []
            switch = sys.getswitchinterval()
            sys.setswitchinterval(1.0)
            try:
                with jax.profiler.TraceAnnotation("bench.submit"):
                    for i in range(nxt[0], nxt[0] + batch):
                        rec = Record(index=i, sent=time.perf_counter())
                        fut = eng.submit(reqs[i])
                        fut.add_done_callback(stamp(rec))
                        sent.append((rec, fut))
            finally:
                sys.setswitchinterval(switch)
            nxt[0] += batch
            with jax.profiler.TraceAnnotation("bench.wait"):
                for rec, fut in sent:
                    try:
                        rec.result = fut.result(timeout=RESULT_TIMEOUT_S)
                    except Exception as exc:  # noqa: BLE001 - a failed one
                        rec.error = exc
                    rec.done = rec.done or time.perf_counter()
                    records.append(rec)

    thread = threading.Thread(target=caller, daemon=True, name="bench-caller")
    thread.start()
    time.sleep(max(0.0, t0 - time.perf_counter()))
    at_open()
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        time.sleep(max(0.0, t_end - time.perf_counter()))
    return Window(t0, t_end, records, [], 0), thread, nxt


def drive(eng, mix: dict, reqs: list, seconds: float, at_open=None,
          at_close=None) -> Window:
    """Drive the window, then wait for every request sent in it.
    ``at_open()`` runs as the window opens (the caller has been sending
    since ``ramp_s`` before it), ``at_close()`` as it closes, before the
    wait."""
    if mix["loop"] != "batch":
        raise ValueError(f"unknown loop {mix['loop']!r}: only \"batch\"")
    win, thread, nxt = _batch_loop(
        eng, reqs, int(mix["batch"]), seconds, float(mix["ramp_s"]),
        at_open or (lambda: None))
    if at_close is not None:
        at_close()
    thread.join(RESULT_TIMEOUT_S + 10.0)
    if thread.is_alive():
        raise RuntimeError("the caller did not get its last answers")
    if nxt[0] + int(mix["batch"]) > len(reqs):
        raise RuntimeError(f"the mix's {len(reqs)} requests ran out inside "
                           "the window: raise max_requests")
    win.records.sort(key=lambda r: r.index)
    win.ok = [r for r in win.records
              if r.error is None and r.result is not None]
    win.n_failed = len(win.records) - len(win.ok)
    for r in win.records:
        if r.error is not None:
            log(f"request {r.index} failed: {r.error!r}")
    return win


@dataclasses.dataclass
class Built:
    """A cell after set-up: the engine, its towers, the data, the requests."""

    eng: object
    cheap: towers.CountedTower
    expensive: towers.CountedTower
    params: dict  # "cheap" / "expensive" -> the tower's weights
    sizes: dict  # "cheap" / "expensive" -> the tower's sizes
    data: data_mod.Data
    reqs: list  # the window's requests (warm-up ones already served)
    phases: dict


def build(cfg: dict, mix: dict, seed: int, bench: Path,
          counter: CompileCounter | None = None) -> Built:
    """Set-up: weights, data, the engine's index build, the warm-up. Each
    tower's module is found under ``bench``."""
    from repro.core.vamana import VamanaConfig
    from repro.serve import BiMetricEngine, SearchRequest

    phases = {}
    t = time.perf_counter()

    def phase(label):
        nonlocal t
        now = time.perf_counter()
        phases[label] = now - t
        log(f"setup phase {label}: {phases[label]:.3f} s"
            + (f"; {counter.take()}" if counter else ""))
        t = now

    key = model.seed_key(seed)
    sizes = {k: model.tower_dict(cfg, k, bench)
             for k in ("cheap", "expensive")}
    params = {k: model.make_params(jax.random.fold_in(key, i), sizes[k])
              for i, k in enumerate(("cheap", "expensive"))}
    phase("weights")
    # both towers read the same token ids: draw them from the smaller table
    vocab = min(int(p["embed"].shape[0]) for p in params.values())
    data = data_mod.make(seed, cfg, int(mix["warmup_requests"]),
                         int(mix["max_requests"]), vocab)
    reqs = [SearchRequest(tokens=q, quota=int(mix["quota"]), k=int(mix["k"]))
            for q in data.queries]
    docs = {row.tobytes() for row in data.corpus}
    cheap, expensive = (
        towers.CountedTower(params[k], model.program_config(sizes[k], k))
        .attach(k, docs) for k in ("cheap", "expensive"))
    phase("data")
    e = cfg["engine"]
    eng = BiMetricEngine(
        cheap, expensive, data.corpus,
        index_cfg=VamanaConfig(**cfg["index"]),
        tower_batch=int(e["tower_batch"]), backend=e["backend"],
        slots=int(e["slots"]))
    phase("index_build")
    for f in [eng.submit(r) for r in reqs[:data.n_warmup]]:
        f.result(timeout=RESULT_TIMEOUT_S)
    phase("warmup")
    log(f"engine backend {eng.backend.name} interpret={eng.backend.interpret}"
        f" slots {eng.slots} tower_batch {eng.tower_batch}")
    return Built(eng, cheap, expensive, params, sizes, data,
                 reqs[data.n_warmup:], phases)


def run_cell(spec, name: str, seed: int, seconds: float, traced: bool, *,
             t_start: float, trace_dir: Path, control: bool = False,
             cfg: dict | None = None, mix: dict | None = None,
             patch=None) -> dict:
    """Run cell ``name`` once; returns the result line as a dict.

    ``cfg`` / ``mix`` replace the cell's files (tests run the same path
    at a small size); ``patch(engine)`` breaks the timed path underneath
    (fault tests); ``control`` also reads the controls' gaps."""
    w = spec.cell(name)
    cfg = cfg or spec.load_config(w["config"])
    mix = mix or spec.load_traffic(w["traffic"])
    dev = jax.devices()[0]
    counter = CompileCounter()
    counter.on = True
    b = build(cfg, mix, seed, spec.bench, counter)
    if patch is not None:
        patch(b.eng)
    tracer = None
    if traced:
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir.mkdir(parents=True)
        tracer = trace.capture(str(trace_dir))
    # every request sent, from the ramp on, is counted from here
    c_start = {k: getattr(b, k).snapshot() for k in ("cheap", "expensive")}
    b.cheap.recording = True
    c0, c1 = {}, {}
    setup = {}

    def at_open():
        setup["s"] = time.perf_counter() - t_start
        log(f"setup before the window (the ramp included): "
            f"{counter.take()}")
        c0.update(cheap=b.cheap.snapshot(), expensive=b.expensive.snapshot())
        if tracer is not None:
            tracer.__enter__()

    def at_close():
        c1.update(cheap=b.cheap.snapshot(), expensive=b.expensive.snapshot())
        if tracer is not None:
            tracer.__exit__(None, None, None)
        log(f"compiles inside the window: {counter.take()}")
        counter.on = False

    win = drive(b.eng, mix, b.reqs, seconds, at_open, at_close)
    b.cheap.recording = False
    setup_s = setup["s"]
    mem = dev.memory_stats() or {}
    memory_peak = int(mem.get("peak_bytes_in_use", 0))

    ctx = {
        "mix": mix, "cfg": cfg, "setup_s": setup_s, "window_s": seconds,
        "t0": win.t0, "t_end": win.t_end, "records": win.records,
        # between the window's opening and its close
        "towers": {k: towers.diff(c0[k], c1[k]) for k in c0},
        # every request sent, from the ramp's start until the last resolved
        "towers_all": {k: towers.diff(c_start[k], getattr(b, k).snapshot())
                       for k in c_start},
        "tower_sizes": {k: (b.sizes[k], cfg["query_len"]) for k in b.sizes},
        "device_kind": dev.device_kind,
    }
    if traced:
        t = time.perf_counter()
        ctx["trace"] = trace.reduce(trace.find_xplane(str(trace_dir)))
        log(f"trace reduced in {time.perf_counter() - t:.3f} s: window "
            f"{ctx['trace'].window_s:.3f} s, device busy "
            f"{ctx['trace'].busy_s:.3f} s")
    in_window = sum(1 for r in win.ok if win.t0 <= r.done <= win.t_end)
    log(f"window: {len(win.records)} requests sent from the ramp on, "
        f"{in_window} resolved inside the window, {win.n_failed} failed; "
        f"tower rows (calls/asked/useful/computed/doc) in the window: cheap "
        f"{dataclasses.astuple(ctx['towers']['cheap'])}, expensive "
        f"{dataclasses.astuple(ctx['towers']['expensive'])}; expensive from "
        f"the ramp on {dataclasses.astuple(ctx['towers_all']['expensive'])}")

    spent = [r.result.stats.D_calls for r in win.ok]
    if spent:
        log(f"D_calls per request: min {min(spent)}, median "
            f"{sorted(spent)[len(spent) // 2]}, max {max(spent)}; "
            f"{sum(x < int(mix['quota']) for x in spent)} of {len(spent)} "
            f"under the quota")

    kind = "per_layer" if traced else "end_to_end"
    metrics = {}
    for m in spec.cell_metrics(name, kind):
        value = spec.reader(m)(ctx)
        if value is not None:
            metrics[m] = {"value": float(value),
                          "unit": spec.metrics[m]["unit"]}

    numbers = check.exact_counts(
        [r.result for r in win.ok], win.n_failed, quota=int(mix["quota"]),
        k=int(mix["k"]), n_docs=cfg["n_docs"])
    # the reference runs once the program's state is freed
    b.eng.close()
    params, sizes, data, phases = b.params, b.sizes, b.data, b.phases
    cheap_out = b.cheap.outputs
    del b
    gc.collect()
    t = time.perf_counter()
    chosen = [win.ok[i] for i in check.sample(
        len(win.ok), int(mix["check_sample"]), seed)]
    qidx = [data.n_warmup + r.index for r in chosen]
    ref = check.load_reference(cfg, spec.bench)
    numbers.update(check.reference_gaps(
        ref, params["expensive"], sizes["expensive"], data.corpus,
        data.queries[qidx], data.planted[qidx],
        [(r.result.ids, r.result.dists) for r in chosen],
        precisions=("float32", "fp8") if control else ("float32",)))
    numbers.update(check.cheap_gaps(
        ref, params["cheap"], sizes["cheap"], data.queries[qidx], cheap_out,
        controls=("bf16", "fp8") if control else ()))
    log(f"reference check of {len(chosen)} requests: "
        f"{time.perf_counter() - t:.3f} s")
    correct, checks = check.verdict(numbers, cfg["limits"])
    for k in ("dist_gap", "nn_miss", "cheap_gap"):
        if k not in checks:
            log(f"reading {k}: {numbers[k]!r} (not compared in this cell)")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": jax.device_count(), "memory_peak_bytes": memory_peak}
    out = {"correct": correct, "attempted": len(win.records),
           "failed": win.n_failed, "metrics": metrics, "device": device}
    if traced:
        device["busy_s"] = ctx["trace"].busy_s
        device["window_s"] = ctx["trace"].window_s
        out["breakdown"] = trace.breakdown(ctx["trace"])
    if control:
        out["control"] = {k: v for k, v in numbers.items()
                          if k.startswith("control_")}
        out["readings"] = {k: v for k, v in numbers.items()
                           if not k.startswith("control_")}
    out["setup_phases"] = phases
    out["checks"] = checks
    return out
