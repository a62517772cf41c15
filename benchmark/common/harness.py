"""Run one cell once: set up, warm up, measure the window, check the
answers against the plain reference, and build the result line.

Everything a cell needs is found by name under ``root``:
``BENCHMARK.json`` names the cell's configuration file and traffic mix;
the mix is ``benchmark/traffic/<traffic>.json``; each metric is read by
``benchmark/metrics/<metric>.py`` (a ``read(run)`` that returns a number,
or None where it finds nothing to read); each query shape the mix names
is ``benchmark/shapes/<shape>.py`` (``queries.py``); the configuration
names its tables and its plain reference module.
"""

from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import shutil
import statistics
import sys
import threading
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Optional


from . import compare, guard, queries, trace_reduce
from .keys import stream
from .system import Deployment
from .traffic import Keyspace, Stream, key_columns

WORK = ".benchmark_work"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(f"bench_{path.stem.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Bench:
    """``BENCHMARK.json`` and the files it names, under ``root``."""

    def __init__(self, root: Path):
        self.root = Path(root)
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())

    def cell(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        entry = next(c for c in self.spec["configs"] if c["name"] == name)
        return json.loads((self.root / entry["file"]).read_text())

    def traffic(self, name: str) -> dict:
        return json.loads((self.root / "benchmark" / "traffic" / f"{name}.json").read_text())

    def metrics(self, cell: str, trace: bool) -> list:
        group = self.spec["per_layer"] if trace else self.spec["end_to_end"]
        return [m for m in group if cell in m.get("workloads", [cell])]

    def reader(self, metric: str):
        return load_module(self.root / "benchmark" / "metrics" / f"{metric}.py")

    def reference(self, config: dict):
        return load_module(self.root / config["reference"])

    def shapes(self, mix: dict) -> dict:
        """{name: queries.Shape} of every shape the mix's requests name."""
        names = sorted({r["shape"] for r in mix.get("requests", ())})
        return {n: queries.Shape(load_module(self.root / "benchmark" / "shapes" / f"{n}.py")) for n in names}


@dataclass
class Record:
    """One request of the window, from the client's side."""

    client: int
    shape: str
    params: dict
    t0: float = 0.0
    t1: float = 0.0
    error: Optional[str] = None
    ticket: object = None
    answer: Optional[dict] = None

    @property
    def ok(self) -> bool:
        return self.error is None


@dataclass
class Run:
    """What a metric reader reads."""

    cell: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    device_kind: str = ""
    setup_s: float = 0.0
    window_s: float = 0.0
    records: list = field(default_factory=list)
    builds: list = field(default_factory=list)  # (name, t0, t1, rows)
    counters: dict = field(default_factory=dict)  # moved in the window
    spans: dict = field(default_factory=dict)  # name -> [seconds]
    profile: Optional[dict] = None  # trace_reduce.reduce of the window
    gc_pauses: list = field(default_factory=list)  # (generation, seconds) in the window
    rows: dict = field(default_factory=dict)  # rows per table
    compile: dict = field(default_factory=dict)
    shapes: dict = field(default_factory=dict)  # name -> queries.Shape the mix names


def _annotate(on: bool, name: str):
    if not on:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)


@contextlib.contextmanager
def _gc_pauses(out: list):
    """Record each garbage collection of the process as (generation,
    seconds) while the block runs."""
    t = {}

    def watch(phase, info):
        if phase == "start":
            t["t"] = time.perf_counter()
        elif "t" in t:
            out.append((info["generation"], time.perf_counter() - t.pop("t")))

    gc.callbacks.append(watch)
    try:
        yield
    finally:
        gc.callbacks.remove(watch)


class Profiler:
    """A ``jax.profiler`` trace of the window in a traced run, and the
    monotonic clock of the program's spans mapped onto it."""

    def __init__(self, directory: Path, on: bool):
        self.dir, self.on = directory, on
        self.mono0 = None

    def __enter__(self):
        if self.on:
            import jax

            shutil.rmtree(self.dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(str(self.dir), profiler_options=opts)
            self._note = jax.profiler.TraceAnnotation(trace_reduce.WINDOW)
            self._note.__enter__()
            self.mono0 = time.monotonic()
        return self

    def __exit__(self, *exc):
        if self.on:
            import jax

            self._note.__exit__(None, None, None)
            jax.profiler.stop_trace()
        return False

    def reduce(self, traces) -> dict:
        raw = trace_reduce.load(trace_reduce.xplane_file(str(self.dir)))
        w0, _ = trace_reduce.window_of(raw["annotations"])
        spans = trace_reduce.program_spans(traces, lambda t: w0 + (t - self.mono0) * 1e9)
        out = trace_reduce.reduce(raw, spans)
        top = sorted(out["module_s"].items(), key=lambda kv: -kv[1])[:20]
        log(f"trace: {sum(map(len, raw['ops'].values()))} ops on {len(raw['ops'])} device(s); "
            f"modules by time {json.dumps(top)}")
        shutil.rmtree(self.dir, ignore_errors=True)
        return out


# ---------------------------------------------------------------------------
# query cells
# ---------------------------------------------------------------------------
def _serve(server, dep, shapes: dict, rec: Record, annotate: bool) -> Record:
    shape = shapes[rec.shape]
    df = shape.dataframe(dep.session, dep.paths, rec.params)
    with _annotate(annotate, "bench.submit"):
        rec.t0 = time.perf_counter()
        try:
            rec.ticket = server.submit(df)
        except Exception as e:  # noqa: BLE001 - a refused request is a failed one
            rec.error, rec.t1 = repr(e), time.perf_counter()
            return rec
    with _annotate(annotate, "bench.result"):
        try:
            batch = rec.ticket.result(timeout=600)
        except Exception as e:  # noqa: BLE001 - the request failed
            rec.error = repr(e)
            batch = None
        rec.t1 = time.perf_counter()
    if batch is not None:
        rec.answer = queries.arrays(batch, shape.columns)
    return rec


def _closed_loop(server, dep, shapes: dict, streams, seconds: float, annotate: bool) -> tuple:
    """Each client sends its next request when the last one returns,
    until ``seconds`` have passed; requests in flight then finish.
    Returns (records, window start, last completion)."""
    out = [[] for _ in streams]
    gate = threading.Barrier(len(streams) + 1)
    clock = {}

    def client(c):
        gate.wait()
        end = clock["end"]
        while time.perf_counter() < end:
            shape, params = next(streams[c])
            out[c].append(_serve(server, dep, shapes, Record(c, shape, params), annotate))

    threads = [threading.Thread(target=client, args=(c,), daemon=True) for c in range(len(streams))]
    for t in threads:
        t.start()
    start = time.perf_counter()
    clock["start"], clock["end"] = start, start + seconds
    gate.wait()
    for t in threads:
        t.join()
    records = [r for rs in out for r in rs]
    last = max((r.t1 for r in records), default=start)
    return records, start, last


def _bursts(dep, shapes, traffic, keyspaces, seed):
    """Warm the batched programs of every batch size the window can form:
    a paused one-worker server takes a burst of n requests, then starts
    and serves them as one batch."""
    from hyperspace_tpu.serve import QueryServer, ServeConfig

    sizes = traffic["warmup"].get("burst_sizes", [])
    s = Stream(traffic, keyspaces, seed, 0, 1)
    for n in sizes:
        server = QueryServer(dep.session, ServeConfig(
            max_workers=1, autostart=False, recovery_sweep_interval_s=0))
        try:
            reqs = [next(s) for _ in range(n)]
            tickets = [server.submit(shapes[sh].dataframe(dep.session, dep.paths, p)) for sh, p in reqs]
            server.start()
            for t in tickets:
                t.result(timeout=600)
        finally:
            server.close()


def run_queries(run: Run, dep: Deployment, clock, t_setup0: float) -> None:
    from hyperspace_tpu.exec.hbm_cache import hbm_cache
    from hyperspace_tpu.serve import QueryServer, ServeConfig

    traffic = run.traffic
    keyspaces = {c: Keyspace(dep.column(*c.split(".")), run.seed) for c in key_columns(traffic)}
    clients = traffic["clients"]
    server = QueryServer(dep.session, ServeConfig())
    try:
        # each shape once, serially: the first touch schedules the
        # residency, the second runs (and compiles) the resident programs
        firsts = {}
        for c in range(len(traffic["requests"])):
            shape, params = next(Stream(traffic, keyspaces, run.seed, c, 1))
            firsts.setdefault(shape, params)
        for _ in range(2):
            for shape, params in firsts.items():
                rec = _serve(server, dep, run.shapes, Record(-1, shape, params), False)
                if not rec.ok:
                    raise RuntimeError(f"warm-up {shape} failed: {rec.error}")
            hbm_cache.wait_background(600)
        log(f"set-up: first touches and residency {time.perf_counter() - t_setup0:.3f} s")
        _bursts(dep, run.shapes, traffic, keyspaces, run.seed)
        if traffic["warmup"]["seconds"] > 0:
            warm = [Stream(traffic, keyspaces, run.seed, c, 1) for c in range(clients)]
            recs, _, _ = _closed_loop(server, dep, run.shapes, warm, traffic["warmup"]["seconds"], False)
            bad = [r.error for r in recs if not r.ok]
            if bad:
                raise RuntimeError(f"warm-up requests failed: {bad[:3]}")
            hbm_cache.wait_background(600)

        streams = [Stream(traffic, keyspaces, run.seed, c, 0) for c in range(clients)]
        before, c0 = guard.counters(), clock.reading()
        run.setup_s = time.perf_counter() - t_setup0
        profiler = Profiler(dep.ws / "profile", run.trace)
        with profiler, _gc_pauses(run.gc_pauses):
            records, start, last = _closed_loop(server, dep, run.shapes, streams, run.seconds, run.trace)
        run.counters = guard.moved(before, guard.counters())
        run.compile = {k: v - c0[k] for k, v in clock.reading().items()}
        run.records, run.window_s = records, last - start
        traces = [r.ticket.trace for r in records if r.ticket is not None and r.ticket.trace is not None]
        run.spans = trace_reduce.span_durations(traces)
        if run.trace:
            run.profile = profiler.reduce(traces)
    finally:
        server.close()


def check_queries(run: Run, dep: Deployment, ref_mod) -> dict:
    """The numbers compared for a query cell."""
    ref = ref_mod.Reference({t: ref_mod.concat(dep.tables[t]) for t in run.config["tables"]})
    done = [r for r in run.records if r.ok]
    cap = run.traffic.get("compare_at_most", len(done))
    pick = sorted(stream(run.seed, 6).permutation(len(done))[:cap]) if len(done) > cap else range(len(done))
    cache, wrong, gap = {}, 0, 0.0
    for i in pick:
        r = done[i]
        key = (r.shape, tuple(sorted(r.params.items())))
        if key not in cache:
            cache[key] = ref.answer(r.shape, r.params)
        w, g = run.shapes[r.shape].compare(r.answer, cache[key])
        wrong += w
        gap = max(gap, g)
    return {
        "answers_failed": len(run.records) - len(done),
        "answers_wrong": wrong,
        "max_rel_gap": gap,
    }


# ---------------------------------------------------------------------------
# build cells
# ---------------------------------------------------------------------------
def run_builds(run: Run, dep: Deployment, clock, t_setup0: float) -> None:
    from hyperspace_tpu.telemetry.recorder import flight_recorder

    spec = dep.index_spec(run.traffic["index"])
    rows = dep.rows(spec["table"])
    for k in range(run.traffic["warmup"]["builds"]):
        dep.create_index(spec, f"{spec['name']}_warm{k}")
    before, c0 = guard.counters(), clock.reading()
    run.setup_s = time.perf_counter() - t_setup0
    profiler = Profiler(dep.ws / "profile", run.trace)
    with profiler, _gc_pauses(run.gc_pauses):
        start = time.perf_counter()
        end, k = start + run.seconds, 0
        while time.perf_counter() < end:
            name = f"{spec['name']}_b{k}"
            t0 = time.perf_counter()
            with _annotate(run.trace, "bench.create_index"):
                dep.create_index(spec, name)
            run.builds.append((name, t0, time.perf_counter(), rows))
            k += 1
    run.counters = guard.moved(before, guard.counters())
    run.compile = {k: v - c0[k] for k, v in clock.reading().items()}
    run.window_s = run.builds[-1][2] - start
    names = {b[0] for b in run.builds}
    traces = [t for t in flight_recorder.last() if t.root.labels.get("index") in names]
    run.spans = trace_reduce.span_durations(traces)
    if run.trace:
        run.profile = profiler.reduce(traces)


def check_builds(run: Run, dep: Deployment, ref_mod) -> dict:
    """The numbers compared for a build cell: the worst of the sampled builds."""
    spec = dep.index_spec(run.traffic["index"])
    source = ref_mod.concat(dep.tables[spec["table"]])
    n = run.traffic.get("compare_at_most", len(run.builds))
    pick = sorted(stream(run.seed, 6).permutation(len(run.builds))[:n])
    out = {"rows_wrong": 0, "rows_misbucketed": 0, "order_breaks": 0}
    for i in pick:
        got = ref_mod.index_numbers(
            dep.index_files(run.builds[i][0]), source, spec["indexed"][0],
            spec["included"], dep.config["num_buckets"],
        )
        for k in out:
            out[k] = max(out[k], got[k])
    return out


DRIVERS = {"queries": (run_queries, check_queries), "builds": (run_builds, check_builds)}


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------
def device_info(chips: int) -> dict:
    import jax

    devs = jax.devices()[:chips]
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def run_cell(root: Path, workload: str, seed: int, seconds: float, trace: bool,
             clock, t0: Optional[float] = None) -> dict:
    """The result line of one run of ``workload`` (the device check is the
    caller's)."""
    t0 = time.perf_counter() if t0 is None else t0
    bench = Bench(root)
    cell = bench.cell(workload)
    config = bench.config(cell["config"])
    traffic = bench.traffic(cell["traffic"])
    run = Run(cell, config, traffic, seed, seconds, trace, shapes=bench.shapes(traffic))
    work = Path(root) / WORK
    shutil.rmtree(work, ignore_errors=True)
    dep = Deployment(config, seed, work)
    try:
        dep.generate()
        log(f"set-up: data {time.perf_counter() - t0:.3f} s")
        dep.open()
        if traffic.get("indexes", True):
            dep.build_indexes()
            log(f"set-up: indexes {time.perf_counter() - t0:.3f} s")
        dep.append()
        run.rows = {t: dep.rows(t) for t in config["tables"]}
        drive, check = DRIVERS[traffic["kind"]]
        drive(run, dep, clock, t0)
        device = device_info(cell["chips"])
        run.device_kind = device["kind"]
        dep.close()

        required = traffic.get("device_counters", []) + config.get("device_counters", {}).get(traffic["kind"], [])
        fallbacks, missing = guard.check(run.counters, required, traffic.get("forbidden_counters", ()))
        paths = {k: v for k, v in sorted(run.counters.items())
                 if k.startswith(("scan.path.", "join.path.", "build.engine.", "aggregate.path.", "serve.batch."))}
        log(f"window counters: {json.dumps(paths)}")
        if fallbacks or missing:
            log(f"device-path guard: fallbacks moved {json.dumps(fallbacks)}; required groups not moved {missing}")
        log(f"window compiles: {json.dumps(run.compile)}")
        by_shape = {}
        for r in run.records:
            by_shape.setdefault(r.shape, []).append(r)
        for shape, recs in sorted(by_shape.items()):
            lat = [(r.t1 - r.t0) * 1e3 for r in recs]
            sizes = [r.ticket.batch_size for r in recs if r.ticket is not None]
            log(f"window {shape}: {len(lat)} requests, ms at 10..90% "
                f"{[round(q, 1) for q in statistics.quantiles(lat, n=10)] if len(lat) > 1 else lat}, "
                f"max {max(lat):.3f}; served alone {sizes.count(1)}, "
                f"mean batch {statistics.fmean(sizes) if sizes else 0:.2f}")
        gen2 = [s for g, s in run.gc_pauses if g == 2]
        log(f"window gc: {len(run.gc_pauses)} collections, {sum(s for _, s in run.gc_pauses):.3f} s, "
            f"longest {max((s for _, s in run.gc_pauses), default=0):.3f} s; "
            f"{len(gen2)} of generation 2, {sum(gen2):.3f} s")
        log(f"window: {run.window_s:.3f} s from its start to the last answer")

        numbers = check(run, dep, bench.reference(config))
        numbers["fallback_counters"] = len(fallbacks)
        numbers["device_paths_missing"] = len(missing)
        correct, compared = compare.verdict(numbers, traffic["limits"])

        metrics = {}
        for m in bench.metrics(workload, trace):
            value = bench.reader(m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if trace and run.profile is not None:
            device["busy_s"] = run.profile["busy_s"]
            device["window_s"] = run.profile["window_s"]
        attempted = len(run.records) if traffic["kind"] == "queries" else len(run.builds)
        result = {
            "correct": correct,
            "attempted": attempted,
            "failed": numbers.get("answers_failed", 0),
            "metrics": metrics,
            "device": device,
        }
        if trace and run.profile is not None:
            result["breakdown"] = {
                "device_ops": run.profile["device_ops"],
                "idle_gaps": run.profile["idle_gaps"],
            }
        result["compared"] = compared
        log(f"compile (whole run): {json.dumps(clock.reading())}")
        for name, c in compared.items():
            log(f"compared {name}: {c['value']!r} (limit {c['limit']!r})")
        return result
    finally:
        shutil.rmtree(work, ignore_errors=True)

