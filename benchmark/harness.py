"""What every cell's run shares: finding a cell's files by name, the
device gate, the compile cache's place, the compile clock, set-up phases
and the result line.  Nothing here knows a configuration, a traffic mix
or a per-layer metric; those are files of their own that are found by
the names BENCHMARK.json and the workload files give.
"""

import contextlib
import glob
import importlib.util
import json
import os
import shutil
import time

BENCH_ROOT = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(BENCH_ROOT)
# one fixed place inside the checkout: the directory is part of the
# persistent cache's key, so one that moved would never hit
CACHE_DIR = os.path.join(CHECKOUT, ".jax_cache")
WORK_DIR = os.path.join(CHECKOUT, ".bench_work")


class Lookup:
    """Files of the benchmark by kind and name.  `roots` are searched in
    order; the benchmark's own directory is always last, so a test can
    bring a tiny configuration and workload of its own without an edit
    to any file here."""

    def __init__(self, extra_roots=()):
        self.roots = [os.path.abspath(r) for r in extra_roots]
        self.roots.append(BENCH_ROOT)
        self._modules = {}

    def path(self, kind, filename):
        for root in self.roots:
            candidate = os.path.join(root, kind, filename)
            if os.path.isfile(candidate):
                return candidate
        raise FileNotFoundError(
            "no %s/%s under %s" % (kind, filename, self.roots))

    def json(self, kind, name):
        with open(self.path(kind, name + ".json")) as f:
            return json.load(f)

    def module(self, kind, name):
        path = self.path(kind, name + ".py")
        if path not in self._modules:
            spec = importlib.util.spec_from_file_location(
                "bench_%s_%s" % (kind, name.replace("-", "_")), path)
            module = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(module)
            self._modules[path] = module
        return self._modules[path]

    def names(self, kind):
        """The name of every module of a kind, over all roots."""
        return sorted({os.path.basename(p)[:-len(".py")]
                       for root in self.roots
                       for p in glob.glob(os.path.join(root, kind, "*.py"))})


class CompileClock:
    """What JAX compiled, from `jax.monitoring` (a copy of
    chip_smoke.CompileClock, which counts the same events): backend
    compile calls and the seconds inside them (a persistent-cache hit is
    such a call and counts its load time), and the persistent cache's
    hits and misses."""

    def __init__(self):
        import jax.monitoring

        self.compiles = self.hits = self.misses = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += duration

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return {"compiles": self.compiles, "seconds": self.seconds,
                "hits": self.hits, "misses": self.misses}

    def since(self, before):
        now = self.snapshot()
        return {k: now[k] - before[k] for k in now}


class SetupClock:
    """Set-up by phase, on the host clock, from the start of the process
    to the first instant of the measured window."""

    def __init__(self, process_start):
        self.process_start = process_start

    @contextlib.contextmanager
    def phase(self, name):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            print("setup %-12s %8.3f s"
                  % (name, time.perf_counter() - t0), flush=True)

    def setup_s(self, window_start):
        return window_start - self.process_start


def place_compile_cache():
    """JAX's persistent compilation cache: where the environment says,
    else at the one fixed path in the checkout.  Every compile is kept,
    so that a second run of a cell finds all of its programs."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES" not in os.environ:
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def cache_entries(path):
    try:
        return len(os.listdir(path))
    except OSError:
        return 0


def require_devices(chips, lookup):
    """The cell's devices and their published peaks.

    A device whose kind is not in peaks.json is an error, and so is a
    host with fewer chips than the cell asks for.  The CPU is accepted
    only when the caller asked for it in so many words
    (JAX_PLATFORMS=cpu): that is a rehearsal, its line names the CPU and
    carries no device metric, and `peaks` is None."""
    import jax

    devices = jax.devices()
    first = devices[0]
    if len(devices) < chips:
        raise SystemExit("benchmark: the cell needs %d chip(s), JAX has %d"
                         % (chips, len(devices)))
    if first.platform == "cpu":
        if os.environ.get("JAX_PLATFORMS") != "cpu":
            raise SystemExit(
                "benchmark: JAX found no accelerator (platform cpu); set "
                "JAX_PLATFORMS=cpu to rehearse on the CPU on purpose")
        return devices[:chips], None
    with open(lookup.path("", "peaks.json")) as f:
        table = json.load(f)["devices"]
    if first.device_kind not in table:
        raise SystemExit("benchmark: device kind %r is not in peaks.json"
                         % first.device_kind)
    return devices[:chips], table[first.device_kind]


def memory_peak_bytes(devices):
    """Peak bytes of device memory taken on the fullest of `devices`; 0
    where the backend does not report it (the CPU).

    The TPU runtime counts the arrays a process holds under
    `peak_bytes_in_use` and the scratch memory of the programs it ran
    (XLA's temporaries: a training step's activations) apart, under
    `peak_bytes_reserved`; the chip has given up both, so the peak is
    their sum.  (Seen on the chip: a program with 2 GiB of temporaries
    left bytes_in_use unchanged and bytes_reserved at 2 GiB.)"""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return peak


def work_dir(name):
    path = os.path.join(WORK_DIR, name)
    os.makedirs(path, exist_ok=True)
    return path


class Run:
    """One run of one cell: what the harness gives a driver, and what
    the driver and the trace give the per-layer readers.

    A driver sets `correct`, `attempted`, `failed`, the cell's
    `end_to_end` values ({name: (value, unit)}) and whatever it counted
    in `facts`; it calls `start_window()` at the first instant of the
    measured window."""

    def __init__(self, workload, config, seed, seconds, trace, lookup,
                 devices, peaks, clock, compiles):
        self.workload, self.config = workload, config
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.lookup, self.devices, self.peaks = lookup, devices, peaks
        self.clock, self.compiles = clock, compiles
        self.correct, self.attempted, self.failed = False, 0, 0
        self.end_to_end = {}
        self.facts = {}
        self.window_start = None
        self.trace_dir = None
        self.reduced = None     # benchmark/reduce/xplane.Trace, traced runs
        # (start, end, name) in seconds from the traced window's start:
        # spans the driver learned of outside its own process
        self.host_spans = []
        self._tracing = False

    def start_window(self):
        self.window_start = time.perf_counter()
        print("setup total        %8.3f s"
              % self.clock.setup_s(self.window_start), flush=True)

    def span(self, name):
        """A span of the benchmark's own in the profiler's trace, around
        a call into the program; nothing outside a traced window."""
        if not self._tracing:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    @contextlib.contextmanager
    def tracing(self):
        """Profile what runs inside, into the cell's trace directory,
        with the python tracer off (it would slow the host path that is
        being measured) and one "bench/window" span over all of it."""
        import jax

        self.trace_dir = os.path.join(work_dir(self.workload["name"]),
                                      "trace")
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        self._tracing = True
        try:
            with jax.profiler.TraceAnnotation("bench/window"):
                yield
        finally:
            self._tracing = False
            jax.profiler.stop_trace()
