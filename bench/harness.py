"""The benchmark's harness: everything a run shares, whatever its cell.

``run.py`` drives one cell through four steps, and this module holds
the pieces:

1. find the cell, its configuration and its traffic mix by name in
   ``BENCHMARK.json`` and the files under ``bench/``;
2. insist on the accelerator the cell asks for (no fallback to the CPU);
3. hand the traffic mix's driver (``bench/drivers/<driver>.py``) a
   :class:`Run`, through which it marks the measured window; the run
   counts compilations in and around the window, snapshots the
   program's counters, and, in a traced run, records the profiler trace
   and the program's spans of exactly that window;
4. print the result: earlier lines with compile and kernel figures, the
   comparison's numbers beside their limits on standard error, and the
   result object as the last line of standard output.
"""
from __future__ import annotations

import contextlib
import importlib
import json
import os
import sys
import threading
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE = os.path.join(BENCH, ".cache")

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class BenchError(RuntimeError):
    """The run cannot produce a valid result (bad cell, no chip, ...)."""


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_spec(parked: bool = False) -> dict:
    """``BENCHMARK.json``; with ``parked``, also the cells kept under
    ``bench/parked/`` (each file holds the entries that would put its
    cell back into ``BENCHMARK.json``), which only the tests and
    ``controls.py`` run."""
    spec = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    if parked:
        d = os.path.join(BENCH, "parked")
        for name in sorted(os.listdir(d)):
            for key, entries in load_json(os.path.join(d, name)).items():
                spec[key] = spec[key] + entries
    return spec


def cell_files(spec: dict, workload: str):
    """(cell, configuration entry, configuration, traffic mix)."""
    cells = {c["name"]: c for c in spec["workloads"]}
    if workload not in cells:
        raise BenchError(f"unknown workload {workload!r}; "
                         f"BENCHMARK.json has {sorted(cells)}")
    cell = cells[workload]
    entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    config = load_json(os.path.join(ROOT, entry["file"]))
    traffic = load_json(os.path.join(BENCH, "traffic",
                                     cell["traffic"] + ".json"))
    return cell, entry, config, traffic


def metrics_of(spec: dict, kind: str, workload: str, e2e_names=()):
    """The metric entries of ``kind`` that a cell reports.

    A metric with ``workloads`` lists its cells; an end-to-end metric
    without it is reported everywhere, and a per-layer metric without
    it wherever the end-to-end metric it ``moves`` is."""
    out = []
    for m in spec[kind]:
        if "workloads" in m:
            if workload in m["workloads"]:
                out.append(m)
        elif kind == "end_to_end" or m["moves"] in e2e_names:
            out.append(m)
    return out


def require_chips(n_chips: int):
    """The devices, or BenchError when JAX finds no TPU or too few."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX sees {len(devs)} "
                         f"{devs[0].platform} device(s); this benchmark "
                         f"measures the accelerator and never falls back")
    if len(devs) < n_chips:
        raise BenchError(f"the cell needs {n_chips} chips, JAX sees "
                         f"{len(devs)}")
    return devs[:n_chips]


def configure_jax_cache():
    """JAX's persistent compilation cache at one fixed path inside the
    checkout, caching every program however fast it compiled, so that
    only a cell's first run in a checkout compiles."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(CACHE, "jax")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "-1"


class Compiles:
    """Executable builds with the time each ended, from JAX's monitoring
    events: ``events`` are compiles, ``loads`` are executables read back
    from the persistent cache.  JAX reports both through the same
    duration event; a load is the one that follows a cache-hit event on
    the same thread."""

    def __init__(self):
        self.events = []          # (end perf_counter, seconds, name)
        self.loads = []           # (end perf_counter, seconds, name)
        self._lock = threading.Lock()
        self._hit = threading.local()

    def install(self):
        import jax

        def on_duration(event, secs, **kw):
            if event == COMPILE_EVENT:
                rec = (time.perf_counter(), secs,
                       str(kw.get("fun_name", "?")))
                hit = getattr(self._hit, "pending", False)
                self._hit.pending = False
                with self._lock:
                    (self.loads if hit else self.events).append(rec)

        def on_event(event, **_):
            if event == CACHE_HIT_EVENT:
                self._hit.pending = True

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def between(self, t0, t1, loads=False):
        """(seconds, name) of the compiles (or loads) that ended in
        [t0, t1]."""
        with self._lock:
            return [(s, n) for t, s, n in (self.loads if loads
                                           else self.events)
                    if t0 <= t <= t1]


class Run:
    """What a traffic driver sees of the harness.

    The traffic driver calls :meth:`window_start` and :meth:`window_end`
    (with the instants, on the ``time.perf_counter`` clock, that bound
    the measured work) and :meth:`annotate` around each call it makes
    into the program, so that idle gaps in a trace can be attributed."""

    def __init__(self, args, cell, config_entry, config, traffic, t_start):
        self.seed = int(args.seed)
        self.seconds = float(args.seconds)
        self.trace = bool(args.trace)
        self.cell = cell
        self.config_entry = config_entry
        self.config = config
        self.traffic = traffic
        self.t_start = t_start
        self.compiles = Compiles()
        self.t0 = self.t1 = None
        self.counters0 = self.counters1 = None
        self.trace_dir = os.path.join(CACHE, "trace",
                                      cell["name"].replace("/", "_"))
        self._tracing = False
        self._anchor = None       # perf_counter seconds of the anchor

    # -- window ------------------------------------------------------------
    def begin_trace(self):
        """Start the profiler (traced runs only; a no-op otherwise).
        Called at or before the window's start, on any thread."""
        if not self.trace or self._tracing:
            return
        import shutil

        import jax

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self._tracing = True
        # an annotation whose start is known on both clocks ties the
        # program's perf_counter spans to the profiler's timeline
        with jax.profiler.TraceAnnotation("bench.clock_anchor"):
            self._anchor = time.perf_counter_ns() / 1e9

    def window_start(self, t0=None, counters=None):
        """Mark the start of the measured window: now, or an earlier
        instant ``t0`` with the program's counters as they were then."""
        from repro import obs

        self.begin_trace()
        self.counters0 = obs.snapshot() if counters is None else counters
        self.t0 = time.perf_counter() if t0 is None else t0

    def window_end(self, t1=None, counters=None):
        from repro import obs

        self.t1 = time.perf_counter() if t1 is None else t1
        self.counters1 = obs.snapshot() if counters is None else counters
        if self._tracing:
            import jax

            jax.profiler.stop_trace()
            self._tracing = False

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    def annotate(self, name: str, **kw):
        """A profiler annotation around one call into the program (a
        no-op cost when no trace is being taken)."""
        import jax

        return jax.profiler.TraceAnnotation(name, **kw)

    def counter_delta(self, name: str) -> int:
        def val(snap):
            m = (snap or {}).get(name)
            return 0 if m is None else int(m.get("value", 0))
        return val(self.counters1) - val(self.counters0)

    def program_spans(self):
        """The program's obs spans that overlap the window, as
        (name, thread id, start, end) on the perf_counter clock."""
        from repro.obs import trace as obs_trace

        base = obs_trace._T0 / 1e9
        out = []
        for ev in obs_trace.events():
            if ev.get("ph") != "X":
                continue
            s = base + ev["ts"] / 1e6
            e = s + ev.get("dur", 0) / 1e6
            if e >= self.t0 and s <= self.t1:
                out.append((ev["name"], ev.get("tid"), s, e))
        return out


@contextlib.contextmanager
def quiet_stdout():
    """Route stray prints of the program to standard error, so the
    result stays the last line of standard output."""
    saved = sys.stdout
    sys.stdout = sys.stderr
    try:
        yield
    finally:
        sys.stdout = saved


def load_driver(traffic: dict):
    name = traffic["driver"]
    return importlib.import_module(f"bench.drivers.{name}")


def load_reader(metric_name: str):
    """(read function, arguments) of a per-layer metric's file."""
    desc = load_json(os.path.join(BENCH, "layer_metrics",
                                  metric_name + ".json"))
    mod = importlib.import_module(f"bench.readers.{desc['reader']}")
    return mod.read, desc.get("args", {})


def peaks_for(kind: str) -> dict:
    table = load_json(os.path.join(BENCH, "peaks.json"))
    if kind not in table["devices"]:
        raise BenchError(f"device kind {kind!r} is not in bench/peaks.json;"
                         f" add its published peaks with their source")
    return table["devices"][kind]
