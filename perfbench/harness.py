"""One run of one cell: set-up, the measured window, the check, the
result line.

A cell names a configuration (``configs/<name>.json``: the collection's
shape and the guarantees it is served under) and a traffic mix
(``traffic/<name>.json``, read by ``traffic.py``).  The mix names its
query kind and its arrival process; each is a module found by that name
(``kinds/<kind>.py``, ``arrivals/<arrivals>.py``), as each per-layer
metric's reader is (``metrics/<family>.py``).  The run builds the
configuration's collection (the same for every seed) with the program's
host Re-Pair builder, serves it from ``QueryServer`` on the chip, warms
every shape with the mix, then drives the scheduler (``submit``,
``tick``, ``take``) for the window.  Afterwards every answer of the
window is compared with the plain reference (``reference.py``).
"""

from __future__ import annotations

import contextlib
import functools
import gc
import importlib.util
import json
import math
import shutil
import statistics
import sys
import time
from pathlib import Path

import numpy as np

import corpus
import loops
import traffic

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent

#: seconds after the window closes that an answer may still come
GRACE_S = 60.0

#: server defaults; a configuration's or a mix's ``server`` overrides them
SERVER_DEFAULTS = {"engine": "pallas", "max_short_len": 256}

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
COMPILE_EVENTS = (BACKEND_COMPILE, "/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration")


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


class CompileMeter:
    """Executables JAX obtains (compiled or read back from the persistent
    cache) and the seconds spent tracing, lowering and obtaining them."""

    def __init__(self, jax):
        self.executables = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, event, duration, **_):
        if event in COMPILE_EVENTS:
            self.seconds += duration
        if event == BACKEND_COMPILE:
            self.executables += 1


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def cell_spec(spec: dict, name: str) -> tuple[dict, dict, dict]:
    """(workload entry, configuration, traffic mix) of cell ``name``."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{sorted(cells)}")
    cell = cells[name]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    cfg = load_json(ROOT / cfg_entry["file"])
    mix = load_json(HERE / "traffic" / f"{cell['traffic']}.json")
    return cell, cfg, mix


def reports(metric: dict, cell: str) -> bool:
    """Whether ``cell`` reports end-to-end ``metric``."""
    return "workloads" not in metric or cell in metric["workloads"]


def layer_reports(metric: dict, cell: str, reported: set) -> bool:
    """Whether ``cell``, which reports the end-to-end metrics
    ``reported``, reports per-layer ``metric``."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric["moves"] in reported


@functools.lru_cache(maxsize=None)
def load_module(kind: str, name: str):
    """Module ``<kind>/<name>.py`` of the benchmark: a query kind, an
    arrival process or a per-layer metric's reader."""
    path = HERE / kind / f"{name}.py"
    if not path.exists():
        raise KeyError(f"no {kind} module {name!r} at {path}")
    spec = importlib.util.spec_from_file_location(
        f"perfbench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def collection(cfg: dict):
    """(posting lists, number of docs) of a configuration: drawn from its
    ``collection_seed``, so every run of a cell serves the same one."""
    return corpus.make_collection(cfg, int(cfg["collection_seed"]))


class Served:
    """The served path as the loops see it: one query in, one answer out."""

    def __init__(self, srv, mix: dict):
        self.sched = srv.scheduler
        self.kind = load_module("kinds", mix["kind"])
        self.mix = mix
        self.srv = srv

    def submit(self, terms) -> int:
        return self.kind.submit(self.srv, terms, self.mix)

    def tick(self) -> int:
        return self.sched.tick()

    def poll(self, qid):
        try:
            return self.sched.take(qid)
        except KeyError:
            return None


def device_bytes(jax) -> int:
    """Bytes of the arrays live on the device, after a collection so that
    arrays nothing refers to are not counted."""
    gc.collect()
    return sum(int(a.nbytes) for a in jax.live_arrays())


def check(cfg: dict, mix: dict, lists, num_docs: int, recs,
          control: bool = False) -> dict:
    """Compare every answer with the reference.  Returns the numbers
    compared, each ``{"value": ..., "limit": ...}``; the run is correct
    when no value passes its limit.  ``control`` puts the reference's
    control in the program's place."""
    unanswered = sum(1 for r in recs if r.answer is None)
    out = {"unanswered": {"value": unanswered, "limit": 0}}
    out.update(load_module("kinds", mix["kind"]).check(
        cfg, mix, lists, num_docs, recs, control))
    return out


def passes(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


def percentile(values, q: float) -> float:
    """Nearest-rank percentile."""
    v = sorted(values)
    return v[max(0, math.ceil(q / 100 * len(v)) - 1)]


class Cell:
    """A cell after set-up: its collection, its server and what set-up
    measured."""

    def __init__(self, spec: dict, name: str, seed: int, seconds: float,
                 t_start: float, require_tpu: bool = True,
                 cfg: dict | None = None, mix: dict | None = None,
                 after_server=None, compile_cache: bool = True,
                 log=sys.stderr):
        """Set the cell up for a window of ``seconds``.  ``cfg`` and
        ``mix`` stand in for the cell's files where given,
        ``after_server`` is called with every server engine the window
        may use, and ``compile_cache`` turns on JAX's persistent
        compilation cache in the checkout."""
        self.spec, self.name, self.seed, self.log = spec, name, seed, log
        self.cell, cfg_file, mix_file = cell_spec(spec, name)
        self.cfg = cfg_file if cfg is None else cfg
        self.mix = mix_file if mix is None else mix
        self.kind = load_module("kinds", self.mix["kind"])
        self.arrivals = load_module("arrivals", self.mix["arrivals"])
        self.after_server = after_server
        sys.path.insert(0, str(ROOT / "src"))
        import jax

        self.jax = jax
        self.devices = jax.devices()
        if require_tpu and self.devices[0].platform != "tpu":
            raise NoChip(f"no TPU: JAX found {len(self.devices)} "
                         f"{self.devices[0].platform} device(s)")
        if len(self.devices) < int(self.cell["chips"]):
            raise NoChip(f"cell {name} needs {self.cell['chips']} chips, "
                         f"JAX found {len(self.devices)}")
        from repro.build import make_builder
        from repro.launch.compile_cache import enable_compile_cache
        from repro.serve.query_serve import QueryServer

        cache_dir = enable_compile_cache() if compile_cache else "off"
        self.meter = CompileMeter(jax)
        print(f"device {self.devices[0].device_kind} x {len(self.devices)}"
              f", compile cache {cache_dir}", file=log, flush=True)

        self.lists, self.num_docs = collection(self.cfg)
        self.postings = int(sum(len(l) for l in self.lists))
        t0 = time.perf_counter()
        res = make_builder("host").build_grammar(self.lists)
        self.build_s = time.perf_counter() - t0
        print(f"collection {self.num_docs} docs, {len(self.lists)} lists, "
              f"{self.postings} postings, {res.grammar.num_rules} rules, "
              f"build {self.build_s:.3f} s", file=log, flush=True)

        self.bytes_before = before = device_bytes(jax)
        self.index_bytes = None
        server = {**SERVER_DEFAULTS, **self.cfg.get("server", {}),
                  **self.mix.get("server", {})}
        self.score_page = server.pop("score_page_size", None)
        self.srv = QueryServer(res, batch_window=self.mix.get("batch_window"),
                               **server)
        if require_tpu and getattr(self.srv.engine, "interpret", False):
            raise RuntimeError("the pallas engine would interpret its "
                               "kernels")
        self._prime(None)
        print(f"server up at {time.perf_counter() - t_start:.3f} s",
              file=log, flush=True)
        self.api = Served(self.srv, self.mix)

        warmed = 0
        for i, phase in enumerate(self.mix["warmup"]):
            if phase.get("replay"):
                warmed += self._replay(seconds)
            else:
                warm = loops.Driver(self.api, time.perf_counter, time.sleep)
                wrecs = self.arrivals.run(
                    warm, {**self.mix, **phase},
                    traffic.QueryStream(self.mix, self.lists, seed, 1 + i),
                    float(phase["seconds"]), seed + 1 + i)
                self.arrivals.finish(warm, wrecs, GRACE_S)
                warmed += len(wrecs)
            print(f"warm-up phase {phase}: done at "
                  f"{time.perf_counter() - t_start:.3f} s", file=log,
                  flush=True)
        primed = device_bytes(jax) - before
        if self.index_bytes is None:
            self.index_bytes = primed
        self.setup_s = time.perf_counter() - t_start
        self.compile_s = self.meter.seconds
        print(f"set-up {self.setup_s:.3f} s (compile {self.compile_s:.3f} "
              f"s, {self.meter.executables} executables), warm-up "
              f"{warmed} queries, index bytes {self.index_bytes} as served,"
              f" {primed} as primed", file=log, flush=True)

    def _prime(self, old) -> None:
        """Ready the serving engine as the window will find it: the
        mix's score page size, what the query kind takes over from ``old``
        (the engine it replaced) where there was one, the tables the
        engine builds on first use (the bys prefix table), and the
        planner's statistics."""
        eng = self.srv.engine
        if self.score_page is not None:
            eng.score_page_size = int(self.score_page)
        self.kind.prime(eng, old)
        if old is not None:
            eng.next_geq_bys_batch(np.zeros(1, np.int32),
                                   np.zeros(1, np.int64))
        self.srv.executor
        if self.after_server is not None:
            self.after_server(self.srv)

    def _replay(self, seconds: float) -> int:
        """Serve the window's own queries, as the window will send them,
        then hand the window a fresh engine over the same index (the
        server's ``swap_index``): every program the window's merged
        rounds need is then compiled, and no cache holds their answers.
        A closed loop runs on past ``seconds`` by the time spent compiling
        and a tenth more, so that it reaches as far as the window will.
        The index's device bytes are read before the swap: the engine then
        holds every table that the window's queries make it build."""
        c0 = self.meter.seconds
        warm = loops.Driver(self.api, time.perf_counter, time.sleep)
        stream = traffic.QueryStream(self.mix, self.lists, self.seed, 0)
        recs = self.arrivals.run(
            warm, self.mix, stream, seconds, self.seed,
            extra=lambda: 1.1 * (self.meter.seconds - c0) + 0.1 * seconds)
        self.arrivals.finish(warm, recs, GRACE_S)
        self.index_bytes = device_bytes(self.jax) - self.bytes_before
        old = self.srv.engine
        self.srv.swap_index(self.srv.res)
        self._prime(old)
        return len(recs)

    def window(self, seconds: float, trace: bool = False, stream: int = 0,
               mix: dict | None = None) -> dict:
        """Drive the mix (or ``mix``) for ``seconds`` from query stream
        ``stream``, then wait for what is still due.  Returns what the
        window measured, with the records of its queries."""
        jax, mix = self.jax, self.mix if mix is None else mix
        arrivals = load_module("arrivals", mix["arrivals"])
        exe0 = self.meter.executables
        st0 = self.srv.serve_stats()
        trace_dir = HERE / ".trace" / self.name
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)

            def span(phase):
                return jax.profiler.TraceAnnotation(f"bench.{phase}")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        else:
            def span(_phase):
                return contextlib.nullcontext()
        driver = loops.Driver(self.api, time.perf_counter, time.sleep, span)
        queries = traffic.QueryStream(mix, self.lists, self.seed, stream)
        with span("window"):
            recs = arrivals.run(driver, mix, queries, seconds,
                                self.seed + stream)
        if trace:
            jax.profiler.stop_trace()
        ws = driver.stats
        w = {"recs": recs, "stats_before": st0,
             "stats_after": self.srv.serve_stats(),
             "executables": self.meter.executables - exe0,
             "memory": self.devices[0].memory_stats() or {},
             "ticks": ws.ticks, "tick_s": ws.tick_s,
             "tick_errors": ws.tick_errors, "trace": None}
        if trace:
            from trace_reduce import find_xplane, load_events, reduce
            path = find_xplane(str(trace_dir))
            w["trace"] = reduce(load_events(path)) if path else None
            shutil.rmtree(trace_dir, ignore_errors=True)
        arrivals.finish(driver, recs, GRACE_S)
        w["attempted"] = arrivals.attempted(recs, ws.end)
        w["completed"] = loops.completed_in_window(recs, ws.end)
        w["latency"] = loops.latency_at_close(recs, ws.end)
        lag = ws.lag_s or [0.0]
        print(f"window {seconds} s: {w['completed']} completed of "
              f"{w['attempted']}, {ws.ticks} ticks, {w['executables']} "
              f"executables obtained, generator lag p95 "
              f"{percentile(lag, 95) * 1e3:.3f} ms max "
              f"{max(lag) * 1e3:.3f} ms, tick errors "
              f"{len(ws.tick_errors)}", file=self.log, flush=True)
        for e in ws.tick_errors[:3]:
            print(f"tick error: {e}", file=self.log)
        return w

    def check(self, w: dict, control: bool = False) -> dict:
        return check(self.cfg, self.mix, self.lists, self.num_docs,
                     w["recs"], control)


def run_cell(spec: dict, name: str, seed: int, seconds: float,
             trace: bool, t_start: float, **kwargs) -> dict:
    """One run of cell ``name``; returns the result line's object."""
    c = Cell(spec, name, seed, seconds, t_start, **kwargs)
    w = c.window(seconds, trace)
    checks = c.check(w)
    recs = w["recs"]
    lat = w["latency"]
    device = {"platform": c.devices[0].platform,
              "kind": c.devices[0].device_kind,
              "count": int(c.cell["chips"]),
              "memory_peak_bytes": int(w["memory"].get("peak_bytes_in_use",
                                                       0))}
    out = {"correct": passes(checks) and not w["tick_errors"],
           "attempted": w["attempted"],
           "failed": sum(1 for r in recs if r.answer is None)}
    e2e = {
        "qps": lambda: w["completed"] / seconds,
        "p95_ms": lambda: percentile(lat, 95) * 1e3,
        "bits_per_posting": lambda: c.index_bytes * 8 / c.postings,
        "setup_s": lambda: c.setup_s,
    }
    reported = {m["name"] for m in spec["end_to_end"] if reports(m, name)}
    metrics = {}
    if not trace:
        for m in spec["end_to_end"]:
            if m["name"] in reported:
                metrics[m["name"]] = {"value": float(e2e[m["name"]]()),
                                      "unit": m["unit"]}
    else:
        ctx = {"cell": name, "mix": c.mix, "seconds": seconds,
               "completed": w["completed"], "ticks": w["ticks"],
               "tick_s": w["tick_s"], "stats_before": w["stats_before"],
               "stats_after": w["stats_after"], "build_s": c.build_s,
               "compile_s": c.compile_s, "trace": w["trace"]}
        for m in spec["per_layer"]:
            if not layer_reports(m, name, reported):
                continue
            family, _, suffix = m["name"].partition(".")
            v = load_module("metrics", family).read(ctx, suffix)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        red = w["trace"]
        if red is not None:
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            out["breakdown"] = {"device_ops": red["device_ops"],
                                "idle_gaps": red["idle_gaps"]}
    out["metrics"] = metrics
    out["device"] = device
    if lat and c.arrivals.OPEN:
        print(f"latency over {len(lat)} due: median "
              f"{statistics.median(lat) * 1e3:.3f} ms, p95 "
              f"{percentile(lat, 95) * 1e3:.3f} ms", file=c.log)
    for k, v in checks.items():
        print(f"check {k} {v['value']} limit {v['limit']}", file=c.log)
    out["checks"] = checks
    return out
