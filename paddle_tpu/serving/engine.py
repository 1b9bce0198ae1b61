"""Inference engine: a pruned Program behind a bucketed-shape compile
cache.

The executor already jit-caches per feed shape (`fluid/executor.py`
`_CompiledProgram`), but online traffic has arbitrary per-request batch
sizes — unbucketed, every new batch size is a fresh XLA trace+compile
on the request path.  The engine pads every batch up to a configured
bucket (and ragged flat token dims up to `token_bucket` multiples, the
same scheme as `DataFeeder`), so the set of compiled shapes is small,
known in advance, and warmable at startup: after `warmup()` no dense
in-bucket request ever pays a compile.  Ragged feeds specialize per
(batch bucket, token bucket, max-seqlen bucket) combination — warmup
covers each batch bucket's smallest such shape; longer sequences still
compile once per new token/seqlen bucket as traffic reaches them.

Recompiles are *measured*, not assumed: `trace_count()` sums the jit
specialization counts of every compiled segment, and each `run()`
compares before/after to classify the batch as a compile-cache hit or
miss (exposed via `metrics.cache_hit_total`/`cache_miss_total`).
"""

import threading
import time

import numpy as np

from ..core.ragged import RaggedTensor
from ..core.scope import Scope, global_scope
from ..core.types import np_dtype
from ..fluid import executor as executor_mod
from ..fluid.data_feeder import DEFAULT_RAGGED_BUCKET

__all__ = ["EngineConfig", "InferenceEngine"]

DEFAULT_BATCH_BUCKETS = (1, 2, 4, 8, 16, 32, 64)


class EngineConfig:
    """Shape-bucketing knobs.

    batch_buckets: ascending batch sizes to pad up to; None disables
        padding entirely (exact-shape execution, offline behavior).
        Batches beyond the largest bucket round up to a multiple of it.
    token_bucket: flat token-length multiple for ragged (LoD) feeds.
    warmup_ragged: also pre-compile the ragged feed path per bucket
        (one-token sequences); dense feeds always warm.
    check_numerics: scan fetch outputs for NaN/Inf on the host after
        each run, feeding `numerics_nonfinite_total{tensor=}` (the
        /healthz nonfinite signal).  Off by default: it costs one
        host pass over the outputs, which matters at large fetch
        sizes (the JSON path re-reads them anyway, so turning it on
        for HTTP serving is cheap in practice).
    """

    def __init__(self, batch_buckets=DEFAULT_BATCH_BUCKETS,
                 token_bucket=DEFAULT_RAGGED_BUCKET, warmup_ragged=True,
                 check_numerics=False):
        if batch_buckets is not None:
            batch_buckets = tuple(sorted(set(int(b) for b in
                                             batch_buckets)))
            if not batch_buckets or batch_buckets[0] < 1:
                raise ValueError("batch_buckets must be positive ints")
        self.batch_buckets = batch_buckets
        self.token_bucket = int(token_bucket)
        self.warmup_ragged = bool(warmup_ragged)
        self.check_numerics = bool(check_numerics)

    def bucket_for(self, batch):
        """Smallest configured bucket >= batch (multiples of the
        largest bucket beyond it)."""
        if self.batch_buckets is None:
            return batch
        for b in self.batch_buckets:
            if batch <= b:
                return b
        top = self.batch_buckets[-1]
        return -(-batch // top) * top


def _ragged_to_sequences(r):
    """Host-side inverse of RaggedTensor.from_sequences (lod_level 1):
    the per-sequence value arrays, padding rows dropped."""
    if r.lod_level != 1:
        raise ValueError("micro-batching supports lod_level-1 inputs; "
                         "got lod_level=%d" % r.lod_level)
    splits = np.asarray(r.row_splits[0])
    values = np.asarray(r.values)
    return [values[splits[i]:splits[i + 1]]
            for i in range(len(splits) - 1)]


def slice_ragged(r, nseq):
    """First `nseq` level-0 sequences of a RaggedTensor, as a host-side
    RaggedTensor (used to strip bucket padding from ragged fetches)."""
    import jax.numpy as jnp

    take = int(nseq)
    out_splits = []
    for rs in r.row_splits:
        rs = np.asarray(rs)
        out_splits.append(rs[:take + 1])
        take = int(rs[take])
    values = np.asarray(r.values)[:take]
    return RaggedTensor(jnp.asarray(values), out_splits, nvalid=take)


class InferenceEngine:
    """A pruned inference Program wrapped into a bucket-padded callable
    with its own parameter scope and executor.

    Feeds accepted by `run()` (all batch-major):
      * dense: numpy array `[B, ...]`
      * ragged: python list of per-sequence arrays, or a lod_level-1
        RaggedTensor (rebucketed if padding is enabled)
    Returns fetch values sliced back to the true batch (`B` rows for
    dense fetches, `B` sequences for ragged ones); fetches without a
    batch-major leading dim (e.g. scalar summaries) pass through.
    """

    def __init__(self, program, feed_names, fetch_list, place=None,
                 config=None, scope=None, metrics=None, feed_meta=None):
        from ..fluid import framework

        self.program = program
        self.feed_names = list(feed_names)
        self.fetch_names = [
            f.name if isinstance(f, framework.Variable) else str(f)
            for f in fetch_list]
        self._exe = executor_mod.Executor(place)
        self.place = self._exe.place
        self.config = config or EngineConfig()
        # scope=None tracks the *current* global scope at each run
        # (offline v2.infer semantics); pass an explicit Scope for an
        # isolated parameter store (from_saved_model does)
        self.scope = scope
        self.metrics = metrics
        self._lock = threading.Lock()
        self.last_warmup_stats = None  # set by warmup()
        # feed_meta: the export-time metadata dict from
        # save_inference_model (dtype as a numpy name string); absent
        # entries fall back to the program's var descs
        exported = feed_meta or {}
        self._feed_meta = {}
        for n in self.feed_names:
            m = exported.get(n)
            if m and m.get("dtype"):
                self._feed_meta[n] = {
                    "shape": list(m["shape"]),
                    "dtype": np.dtype(m["dtype"]),
                    "lod_level": int(m["lod_level"])}
            else:
                self._feed_meta[n] = self._var_meta(n)

    # -- construction -------------------------------------------------------
    @classmethod
    def from_saved_model(cls, dirname, place=None, config=None,
                         metrics=None, model_filename="__model__"):
        """Load a `save_inference_model` export into a fresh scope.
        Bucket hints recorded at export time seed the config unless the
        caller passes one explicitly."""
        from ..fluid import io as fluid_io

        scope = Scope()
        exe = executor_mod.Executor(place)
        with executor_mod.scope_guard(scope):
            program, feed_names, fetch_vars, extra = \
                fluid_io.load_inference_model(
                    dirname, exe, model_filename=model_filename,
                    return_meta=True)
        if config is None:
            hints = extra.get("bucket_hints") or {}
            config = EngineConfig(
                batch_buckets=hints.get("batch_buckets",
                                        DEFAULT_BATCH_BUCKETS),
                token_bucket=hints.get("token_bucket",
                                       DEFAULT_RAGGED_BUCKET))
        return cls(program, feed_names, fetch_vars, place=place,
                   config=config, scope=scope, metrics=metrics,
                   feed_meta=extra.get("feed_meta"))

    def param_devices(self):
        """The devices this engine's parameters are on, read from the
        arrays themselves rather than from the place that was asked
        for."""
        import jax

        scope = self.scope if self.scope is not None else global_scope()
        devices = set()
        for var in self.program.global_block().vars.values():
            val = scope.get(var.name) if var.persistable else None
            if isinstance(val, jax.Array):
                devices |= val.devices()
        return devices

    def _var_meta(self, name):
        var = self.program.global_block().var(name)
        return {"shape": list(var.shape), "dtype": np_dtype(var.dtype),
                "lod_level": var.lod_level}

    # -- compile-cache accounting -------------------------------------------
    def trace_count(self):
        """Total jit specializations across every compiled segment —
        the ground truth for 'did that request recompile'.  Counts the
        jit call path's cache PLUS attribution AOT artifacts (each one
        was a real XLA compile, executor._run_attr_aot); persistent-
        cache `aot` entries stay uncounted — a disk hit is the
        opposite of a recompile."""
        n = 0
        for compiled in self._exe._cache.values():
            for jitted in compiled._jit_cache.values():
                size = getattr(jitted["fn"], "_cache_size", None)
                if size is not None:
                    n += size() or 0
                n += sum(1 for v in jitted.get("attr_aot", {}).values()
                         if v is not False)
        return n

    # -- padding ------------------------------------------------------------
    def _batch_of(self, value):
        if isinstance(value, RaggedTensor):
            return value.nseq(0)
        if isinstance(value, (list, tuple)):
            return len(value)
        shape = getattr(value, "shape", None)
        if shape is not None:  # numpy or device array: no host copy
            return int(shape[0])
        return int(np.asarray(value).shape[0])

    def batch_size(self, feeds):
        sizes = {n: self._batch_of(feeds[n]) for n in self.feed_names
                 if n in feeds}
        if not sizes:
            raise ValueError("feeds name none of %s" % self.feed_names)
        if len(set(sizes.values())) != 1:
            raise ValueError("inconsistent feed batch sizes: %r" % sizes)
        return next(iter(sizes.values()))

    def _pad_dense(self, arr, target):
        arr = np.asarray(arr)
        if arr.shape[0] == target:
            return arr
        pad = np.zeros((target - arr.shape[0],) + arr.shape[1:],
                       arr.dtype)
        return np.concatenate([arr, pad], axis=0)

    def _pad_ragged(self, value, target, dtype):
        seqs = (_ragged_to_sequences(value)
                if isinstance(value, RaggedTensor) else
                [np.asarray(s, dtype=dtype) for s in value])
        trailing = seqs[0].shape[1:] if seqs else ()
        # pad with one-token zero sequences (not empty ones: several
        # sequence kernels divide by length)
        seqs = list(seqs) + [np.zeros((1,) + tuple(trailing), dtype)
                             for _ in range(target - len(seqs))]
        return RaggedTensor.from_sequences(
            seqs, dtype=dtype, bucket=self.config.token_bucket)

    def pad_feeds(self, feeds, true_batch=None):
        """Pad every feed up to the bucket for `true_batch`; returns
        (padded_feed_dict, true_batch, bucket)."""
        if true_batch is None:
            true_batch = self.batch_size(feeds)
        bucket = self.config.bucket_for(true_batch)
        padded = {}
        for name in self.feed_names:
            if name not in feeds:
                raise KeyError("missing feed %r (program expects %s)"
                               % (name, self.feed_names))
            value = feeds[name]
            if self.config.batch_buckets is None:
                # exact-shape mode: hand feeds straight through (list
                # inputs still materialize as RaggedTensors)
                if isinstance(value, (list, tuple)):
                    value = self._pad_ragged(
                        value, len(value), self._feed_meta[name]["dtype"])
                padded[name] = value
                continue
            meta = self._feed_meta[name]
            if meta["lod_level"] > 0 or isinstance(value, RaggedTensor) \
                    or isinstance(value, (list, tuple)):
                padded[name] = self._pad_ragged(value, bucket,
                                                meta["dtype"])
            else:
                padded[name] = self._pad_dense(
                    np.asarray(value, dtype=meta["dtype"]), bucket)
        return padded, true_batch, bucket

    def _slice_fetch(self, value, true_batch, bucket):
        if isinstance(value, RaggedTensor):
            if str(value.values.dtype) == "bfloat16":
                # feed/fetch contract stays f32 (see Executor._to_numpy)
                value = value.with_values(
                    value.values.astype(np.float32))
            if value.nseq(0) == bucket and true_batch < bucket:
                return slice_ragged(value, true_batch)
            return value
        arr = np.asarray(value)
        if arr.dtype.name == "bfloat16":
            # feed/fetch contract stays f32 (see Executor._to_numpy)
            arr = arr.astype(np.float32)
        if arr.ndim and arr.shape[0] == bucket and true_batch < bucket:
            return arr[:true_batch]
        return arr

    # -- execution ----------------------------------------------------------
    def run(self, feeds, timings=None):
        """Pad, execute, slice.  `timings`, when given, receives
        {"pad": s, "compute": s}."""
        import jax

        from ..obs import flight as obs_flight
        from ..obs import trace as obs_trace
        from ..resilience import faults as faults_mod

        # chaos hook: injected transient IOError/latency on the
        # request path (free when no fault plan is active)
        faults_mod.check("serving/run")
        with self._lock, obs_trace.span("serving/engine_run",
                                        cat="serving") as run_span:
            t0 = time.perf_counter()
            padded, true_batch, bucket = self.pad_feeds(feeds)
            t1 = time.perf_counter()
            traces_before = self.trace_count()
            scope = (self.scope if self.scope is not None
                     else global_scope())
            try:
                outs = self._exe.run(self.program, feed=padded,
                                     fetch_list=self.fetch_names,
                                     scope=scope, return_numpy=False)
                jax.block_until_ready(
                    [getattr(o, "values", o) for o in outs
                     if o is not None])
            except Exception as exc:
                obs_flight.on_crash(exc, origin="serving/engine",
                                    batch=true_batch, bucket=bucket)
                raise
            t2 = time.perf_counter()
            compiled = self.trace_count() > traces_before
            run_span.set(batch=true_batch, bucket=bucket,
                         compiled=compiled)
        if self.metrics is not None:
            (self.metrics.cache_miss_total if compiled
             else self.metrics.cache_hit_total).inc()
            self.metrics.observe_stage("pad", t1 - t0)
            self.metrics.observe_stage("compute", t2 - t1)
        if timings is not None:
            timings["pad"] = t1 - t0
            timings["compute"] = t2 - t1
            timings["compiled"] = compiled
            timings["bucket"] = bucket
        sliced = [self._slice_fetch(o, true_batch, bucket) for o in outs]
        if self.config.check_numerics:
            from ..obs import health as obs_health

            obs_health.scan_outputs(zip(self.fetch_names, sliced))
        return sliced

    # -- warmup -------------------------------------------------------------
    def _synthetic_feed(self, meta, batch):
        # non-negative dims are the per-sample (dense) / per-row
        # (ragged values) shape — same filter as DataFeeder's
        # _sample_shape
        shape = tuple(s for s in meta["shape"] if s >= 0)
        if meta["lod_level"] > 0:
            return [np.zeros((1,) + shape, meta["dtype"])
                    for _ in range(batch)]
        return np.zeros((batch,) + shape, meta["dtype"])

    def warmup(self):
        """Compile every batch bucket up front with synthetic zero
        feeds, so no dense in-bucket request pays an XLA trace (ragged
        feeds warm only each batch bucket's smallest token/seqlen
        shape — see the module docstring).  Returns the number of
        buckets warmed.

        With JAX's persistent compilation cache on
        (`utils/compile_cache.enable_compile_cache`), a warmup after
        a restart loads each bucket's executables from disk: the
        programs are traced again, nothing is compiled.
        `last_warmup_stats` records what this warmup actually did:
        buckets, seconds, traces, and the cache's hits and misses."""
        # deploy-time static analysis FIRST — it must run even when
        # bucketing (and thus warmup compiling) is disabled: the
        # engine serves a program it did not build (a
        # load_inference_model export), so check structure, re-derived
        # metas, alias/race hazards and TPU lints before any request
        # can hit an opaque XLA error.  Error findings abort the
        # deploy here with op/var identity; warnings/lints land in the
        # registry (analysis_diagnostics_total{code}) for /metrics.
        from .. import analysis

        hints = (None if self.config.batch_buckets is None
                 else {"batch_buckets": list(self.config.batch_buckets)})
        analysis.check_program(
            self.program, level="full", fetches=list(self.fetch_names),
            bucket_hints=hints, origin="serving_warmup") \
            .raise_on_error()

        if self.config.batch_buckets is None:
            return 0
        has_ragged = any(m["lod_level"] > 0
                         for m in self._feed_meta.values())
        if has_ragged and not self.config.warmup_ragged:
            return 0
        # warmup compiles are startup cost, not traffic: keep them out
        # of the request-path latency histograms and hit/miss counters.
        # Memory/cost attribution is ON for these builds — each
        # segment compiles ONCE through an AOT artifact that is both
        # published and kept for execution (executor._run_attr_aot),
        # so /metrics carries the per-bucket xla_* footprints before
        # traffic arrives at no extra compile cost.  force_attribution
        # is a counting override, so concurrent warmups in one process
        # can't race a flag save/restore.
        from ..obs import health as obs_health
        from ..resilience.retry import RetryPolicy

        # a transient I/O hiccup during a warmup compile must not kill
        # the deploy: each bucket retries before the failure surfaces
        retry = RetryPolicy(max_attempts=3, base_delay=0.05,
                            max_delay=1.0, name="serving_warmup")
        saved_metrics, self.metrics = self.metrics, None
        warmed = 0
        from ..obs import mem as obs_mem
        from ..obs import telemetry as obs_tele

        snap_before = obs_tele.snapshot()
        t0 = time.perf_counter()
        try:
            with obs_health.force_attribution():
                for bucket in self.config.batch_buckets:
                    feeds = {n: self._synthetic_feed(m, bucket)
                             for n, m in self._feed_meta.items()}
                    retry.call(self.run, feeds)
                    warmed += 1
                    # this bucket's full XLA program footprint: its
                    # warmup recompiled every jittable segment at the
                    # bucket's shapes, so the capture store (segment
                    # labels are shape-independent — last compile
                    # wins) now reflects exactly this bucket's
                    # executables.  /healthz "memory" reads the
                    # per-bucket gauges back.
                    obs_mem.record_bucket_bytes(
                        bucket, obs_mem.xla_program_bytes_total())
        finally:
            self.metrics = saved_metrics
        # what this warmup cost and where the executables came from:
        # traces, and persistent-cache loads against fresh compiles
        delta = obs_tele.snapshot_delta(snap_before)
        self.last_warmup_stats = {
            "buckets": warmed,
            "seconds": round(time.perf_counter() - t0, 3),
            "jit_compiles": delta.get("executor_jit_traces_total", 0),
            "pcache_hits": delta.get("compile_cache_hits_total", 0),
            "pcache_misses": delta.get("compile_cache_misses_total",
                                       0),
        }
        from ..obs import registry as registry_mod

        registry_mod.get_registry().gauge(
            "serving_warmup_seconds",
            "wall time of the most recent engine warmup") \
            .set(self.last_warmup_stats["seconds"])
        return warmed
