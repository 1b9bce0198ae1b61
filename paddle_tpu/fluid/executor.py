"""Executor: compiles whole program blocks with XLA and runs them.

TPU-native re-design of the reference executor
(reference: paddle/framework/executor.cc:79 Executor::Run — an op-by-op
interpreter; python/paddle/v2/fluid/executor.py:149).

The reference interprets one op at a time, dispatching a device kernel per
op (executor.cc:119-137).  On TPU that model wastes the compiler: instead we
*lower the whole block to one jitted JAX function* — every op kernel is pure
JAX, so XLA fuses the full forward+backward+optimizer program into a single
executable, with parameters donated for in-place buffer reuse.  Ops that
must touch the host (print/save/load/send/recv/feed/fetch) split the block
into maximal jittable segments, preserving the reference's interleaved
semantics.  An eager per-op mode (`run(..., eager=True)`) reproduces the
reference's interpreter for debugging, per-op profiling and nan checks
(reference: executor.cc:29 FLAGS_check_nan_inf).

FLAGS_verify_program gates a verify-before-first-compile step: the
`paddle_tpu.analysis` subsystem checks structure, re-derived
shape/dtype metas and write/alias hazards once per program version,
raising a `ProgramVerificationError` that names the offending op index
and variable instead of letting a malformed desc surface as an opaque
XLA trace error (docs/ANALYSIS.md).

FLAGS_check_nan_inf scans ONLY the eager path — a jitted segment never
sees the flag.  For compiled programs use `paddle_tpu.obs.health`:
`NumericsMonitor` keeps on-device nonfinite/grad-norm counters inside
the jitted step, and `locate_nonfinite(program, feed)` replays a bad
step eagerly to name the first offending op (docs/OBSERVABILITY.md).
"""

import logging
import time

import numpy as np

import jax
import jax.numpy as jnp

from ..core.scope import Scope, global_scope
from ..core.ragged import RaggedTensor, SelectedRows
from ..core.types import np_dtype, VarType
from ..obs import flight as obs_flight
from ..obs import health as obs_health
from ..obs import mem as obs_mem
from ..obs import telemetry as obs_tele
from ..obs import trace as obs_trace
from ..ops import registry as op_registry
from ..resilience import faults as faults_mod
from ..utils import flags
from . import framework
from . import profiler as profiler_mod

_log = logging.getLogger("paddle_tpu.executor")


class NonfiniteError(FloatingPointError):
    """Raised by the eager FLAGS_check_nan_inf scan, carrying the
    identity of the first offending op so `obs.health.locate_nonfinite`
    can report it structurally (op_index is annotated by the eager
    interpreter loop)."""

    def __init__(self, message, op_type=None, slot=None, var_name=None,
                 op_index=None, nonfinite_count=None):
        super().__init__(message)
        self.op_type = op_type
        self.slot = slot
        self.var_name = var_name
        self.op_index = op_index
        self.nonfinite_count = nonfinite_count


def _check_outputs_finite(op_desc, outs):
    """Eager-mode NaN/Inf scan of op outputs (reference: executor.cc:29
    FLAGS_check_nan_inf + CheckTensorNANOrInf executor.cc:66-77).

    NOTE: only the EAGER interpreter runs this scan — a jitted segment
    never sees the flag (scanning inside a trace would force per-op
    device->host syncs and defeat XLA fusion).  For compiled programs,
    use `paddle_tpu.obs.health`: `NumericsMonitor` for always-on
    on-device nonfinite counters, `locate_nonfinite(program, feed)` to
    replay a bad step eagerly and name the first offending op."""
    for slot, names in (op_desc.outputs or {}).items():
        vals = (outs or {}).get(slot) or []
        for name, val in zip(names, vals):
            arr = getattr(val, "values", val)
            if arr is None or not hasattr(arr, "dtype"):
                continue
            if not np.issubdtype(np.dtype(arr.dtype), np.floating):
                continue
            host = np.asarray(arr)  # one device->host copy per output
            bad = int(host.size - np.isfinite(host).sum())
            if bad:
                raise NonfiniteError(
                    "%d NaN/Inf element(s) in output %r (slot %r) of "
                    "op %r" % (bad, name, slot, op_desc.type),
                    op_type=op_desc.type, slot=slot, var_name=name,
                    nonfinite_count=bad)

__all__ = ["Executor", "Place", "CPUPlace", "TPUPlace", "CUDAPlace",
           "NonfiniteError", "global_scope", "scope_guard", "fetch_var"]

RNG_STATE_NAME = "@RNG_STATE@"


# ---------------------------------------------------------------------------
# Places (reference: paddle/platform/place.h:24-55)
# ---------------------------------------------------------------------------

class Place:
    def device(self):
        raise NotImplementedError

    def __eq__(self, other):
        return type(self) is type(other) and self.__dict__ == other.__dict__

    def __hash__(self):
        return hash((type(self).__name__, tuple(sorted(self.__dict__.items()))))


class CPUPlace(Place):
    def device(self):
        try:
            return jax.devices("cpu")[0]
        except RuntimeError:
            return jax.devices()[0]

    def __repr__(self):
        return "CPUPlace()"


class TPUPlace(Place):
    """The accelerator place.  reference: CUDAPlace (place.h:34) — on this
    framework the accelerator is whatever JAX's default backend exposes
    (a TPU chip in production)."""

    def __init__(self, device_id=0):
        self.device_id = device_id

    def device(self):
        devs = jax.devices()
        if not 0 <= self.device_id < len(devs):
            raise ValueError(
                "%r: the %s platform has %d device(s)"
                % (self, devs[0].platform, len(devs)))
        return devs[self.device_id]

    def __repr__(self):
        return "TPUPlace(%d)" % self.device_id


# API-compat alias: reference tests construct fluid.CUDAPlace(0)
CUDAPlace = TPUPlace


import contextlib


@contextlib.contextmanager
def scope_guard(scope):
    from ..core import scope as scope_mod

    old = scope_mod._global_scope
    scope_mod._global_scope = scope
    try:
        yield
    finally:
        scope_mod._global_scope = old


def fetch_var(name, scope=None, return_numpy=True):
    scope = scope or global_scope()
    val = scope.get(name)
    if return_numpy and isinstance(val, jax.Array):
        return np.asarray(val)
    return val


# ---------------------------------------------------------------------------
# Execution context passed to kernels
# ---------------------------------------------------------------------------

class ExecContext:
    """Handed to every kernel.  Carries the RNG stream and sub-block
    lowering for control-flow ops; pure ops ignore it."""

    def __init__(self, executor_like, program, block_idx, env, rng=None,
                 scope=None, place=None):
        self._exec = executor_like
        self.program = program
        self.block_idx = block_idx
        self.env = env
        self._rng = rng
        self.scope = scope
        self.place = place

    def next_rng(self):
        if self._rng is None:
            raise RuntimeError("op needs RNG but segment has no rng state")
        self._rng, k = jax.random.split(self._rng)
        return k

    @property
    def rng(self):
        return self._rng

    def run_block(self, block_idx, env):
        """Run all ops of a sub-block in-trace against `env` (a dict the
        caller seeds with the sub-block's inputs).  Returns the env.
        This is how control-flow kernels (scan/cond bodies) lower their
        sub-blocks (reference: while_op.cc:48-63 runs a nested Executor)."""
        block_desc = self.program.desc.block(block_idx)
        sub = ExecContext(self._exec, self.program, block_idx, env,
                          rng=self._rng, scope=self.scope, place=self.place)
        for op_desc in block_desc.ops:
            apply_op(sub, op_desc)
        self._rng = sub._rng
        return env


def _env_get(ctx, name):
    env = ctx.env
    if name in env:
        return env[name]
    # a TensorArray read before any write is legal (first array_write
    # creates it); everything else must be fed/persistable/produced
    vd = _find_var_desc_or_none(ctx.program, ctx.block_idx, name)
    if vd is not None and vd.type == VarType.TENSOR_ARRAY:
        return None
    raise KeyError("variable %r is not initialized (op inputs must be fed, "
                   "persistable, or produced earlier in the block)" % name)


def _find_var_desc_or_none(program, block_idx, name):
    bd = program.desc.block(block_idx)
    while True:
        if name in bd.vars:
            return bd.vars[name]
        if bd.parent_idx < 0:
            return None
        bd = program.desc.block(bd.parent_idx)


# what starts an op instance's scope: no scope a kernel opens and no op
# type starts with it, so a reader that looks a scope up by name cannot
# meet an instance
INSTANCE_SIGIL = "~"
# an `op_name` path is split on "/", XLA joins the paths of merged
# instructions with ";", and what follows an "@" never reaches the
# compiled program (a location's `name@callsite`)
_PATH_SYNTAX = str.maketrans("/;@", "...")


def op_instance(op_desc):
    """Which op of its type an op is, as the scope `apply_op` opens inside
    the type's: the sigil and the name of a variable the op is bound to.

    A forward op has the name of its first output (the op's own slot
    order, "@EMPTY@" skipped).  A `<type>_grad` op has the name its
    forward op has: `append_backward` hands it the forward's outputs as
    the `O@<slot>` inputs, in the forward's order, so an op and its
    gradient share an instance and join without a table.  An op that
    updates a parameter (the optimizers' ops) has its `Param`.  A weight
    applied four times gives four instances; two ops of one type that
    write one variable in place share one."""
    names = op_desc.input("Param")
    if not names and op_registry.is_grad_op_type(op_desc.type):
        names = [n for slot, vs in op_desc.inputs.items()
                 if slot.startswith("O@") for n in vs if n != "@EMPTY@"]
    if not names:
        names = [n for n in op_desc.output_names() + op_desc.input_names()
                 if n != "@EMPTY@"] or [op_desc.type]
    return INSTANCE_SIGIL + names[0].translate(_PATH_SYNTAX)


def apply_op(ctx, op_desc):
    """Apply one op's kernel against ctx.env (pure; used both under trace
    and eagerly)."""
    t = op_desc.type
    if op_registry.has_op(t):
        info = op_registry.get_op_info(t)
        kernel = info.kernel
        is_generic_grad = False
    elif op_registry.is_grad_op_type(t) and \
            op_registry.has_op(op_registry.forward_type_of_grad(t)):
        info = op_registry.get_op_info(op_registry.forward_type_of_grad(t))
        kernel = info.grad_kernel
        is_generic_grad = kernel is None
    else:
        raise KeyError("operator %r is not registered" % t)

    ins = {}
    for slot, names in op_desc.inputs.items():
        ins[slot] = [None if n == "@EMPTY@" else _env_get(ctx, n)
                     for n in names]

    # the op's type, and inside it the op's instance, on everything it
    # lowers to: the compiled program's `op_name` metadata, and with it a
    # device trace, says which op of the Program (and, by the `_grad`
    # suffix or the optimizer's type, which pass) an instruction came
    # from.  Trace-time only; no arithmetic changes.
    with jax.named_scope(t), jax.named_scope(op_instance(op_desc)):
        if is_generic_grad:
            outs = op_registry.run_generic_grad(
                ctx, op_registry.forward_type_of_grad(t), ins,
                op_desc.attrs)
        else:
            outs = kernel(ctx, ins, op_desc.attrs)

    for slot, names in op_desc.outputs.items():
        vals = outs.get(slot)
        if vals is None:
            continue
        for name, val in zip(names, vals):
            if val is None or name == "@EMPTY@":
                continue
            ctx.env[name] = val
    return outs


# ---------------------------------------------------------------------------
# Block lowering
# ---------------------------------------------------------------------------

def _op_jittable(op_desc):
    t = op_desc.type
    if op_registry.has_op(t):
        return op_registry.get_op_info(t).jittable
    if op_registry.is_grad_op_type(t):
        ft = op_registry.forward_type_of_grad(t)
        if op_registry.has_op(ft):
            return op_registry.get_op_info(ft).jittable
    raise KeyError("operator %r is not registered" % t)


def _op_uses_rng(op_desc):
    t = op_desc.type
    if op_registry.has_op(t):
        return op_registry.get_op_info(t).uses_rng
    return False


def _segment_block(op_descs):
    """Split into (jittable: bool, [op_desc]) runs."""
    segments = []
    for od in op_descs:
        j = _op_jittable(od)
        if segments and segments[-1][0] == j:
            segments[-1][1].append(od)
        else:
            segments.append((j, [od]))
    return segments


def _value_sig(v):
    # RaggedTensor / SelectedRows carry nested arrays; describe each
    if isinstance(v, RaggedTensor):
        return ("ragged", _value_sig(v.values),
                tuple(_value_sig(np.asarray(rs)) for rs in v.row_splits))
    if isinstance(v, SelectedRows):
        return ("rows", _value_sig(v.values), _value_sig(v.rows))
    shape = getattr(v, "shape", None)
    dtype = getattr(v, "dtype", None)
    if shape is None or dtype is None:
        return ("py", type(v).__name__, repr(v))
    return ("t", tuple(int(s) for s in shape), str(dtype))


def _values_signature_key(named_values):
    """Hashable signature tuple for (name, value) pairs: names sorted,
    each value reduced to its shape/dtype aval (nested container types
    included).  The per-call specialization key of the attribution
    artifacts (`_run_attr_aot`): same segment + same key means the
    same executable."""
    return tuple((str(n), _value_sig(v))
                 for n, v in sorted(named_values,
                                    key=lambda kv: str(kv[0])))


class _CompiledProgram:
    """A lowered program: a list of segment runners sharing a host-side env.

    Compile-key granularity: the python structure here depends only on
    (program version, feed names, fetch names); jax.jit inside re-
    specializes per feed shapes/dtypes automatically.
    """

    def __init__(self, executor, program, block_idx, feed_names, fetch_names):
        self.executor = executor
        self.program = program
        self.block_idx = block_idx
        self.feed_names = list(feed_names)
        self.fetch_names = list(fetch_names)
        block_desc = program.desc.block(block_idx)
        self.segments = _segment_block(block_desc.ops)
        self._jit_cache = {}
        # segments traced so far: a run that raised it was a first run
        self.traces = 0
        self._plan = self._analyze()
        self._donation = self._donation_setup()

    # -- data-flow analysis -------------------------------------------------
    def _analyze(self):
        block_desc = self.program.desc.block(self.block_idx)
        prog_desc = self.program.desc

        def find_vd(name):
            bd = block_desc
            while True:
                if name in bd.vars:
                    return bd.vars[name]
                if bd.parent_idx < 0:
                    return None
                bd = prog_desc.block(bd.parent_idx)

        plan = []
        produced_before = set(self.feed_names)
        # names needed after each segment: fetches + anything read later
        later_reads = [set(self.fetch_names)]
        for (j, ops) in reversed(self.segments):
            reads = set()
            for od in ops:
                reads.update(od.input_names())
            later_reads.append(later_reads[-1] | reads)
        later_reads = list(reversed(later_reads))  # later_reads[i+1] = after seg i

        for i, (jit_ok, ops) in enumerate(self.segments):
            reads, writes, rng = [], [], False
            seen_writes = set()
            for od in ops:
                for n in od.input_names():
                    if n not in seen_writes and n not in reads:
                        reads.append(n)
                for n in od.output_names():
                    if n != "@EMPTY@":
                        seen_writes.add(n)
                        if n not in writes:
                            writes.append(n)
                rng = rng or _op_uses_rng(od)
            persist_writes = [
                n for n in writes
                if (find_vd(n) is not None and find_vd(n).persistable)]
            # outputs that must leave the segment
            needed_later = later_reads[i + 1]
            out_names = [n for n in writes
                         if n in needed_later or n in persist_writes]
            plan.append({
                "jit": jit_ok, "ops": ops, "reads": reads,
                "writes": writes, "outputs": out_names,
                "persist_writes": persist_writes, "rng": rng,
                "label": self._segment_label(i, ops),
            })
        return plan

    def _donation_setup(self):
        """Resolve FLAGS_donation into what _run_jit_segment applies:
        {"mode": off|conservative|auto, "widened": [tuple per segment]
        or None}.  Only "auto" runs the donation-safety analysis
        (analysis/alias.py) — and any analysis failure degrades to
        "conservative": the plan must never be the reason a step
        fails."""
        from .. import analysis

        mode = analysis.donation_mode()
        if mode != "auto":
            return {"mode": mode, "widened": None}
        try:
            plan = analysis.analyze_donation(
                self.program, fetches=self.fetch_names,
                feeds=self.feed_names)
            return {"mode": "auto",
                    "widened": [tuple(s["widened"])
                                for s in plan.segments]}
        except Exception:
            _log.debug("donation analysis failed; falling back to "
                       "conservative donation", exc_info=True)
            return {"mode": "conservative", "widened": None}

    # -- execution ----------------------------------------------------------
    def run(self, scope, feed_env, eager=False):
        executor = self.executor
        env = dict(feed_env)

        def resolve(name):
            if name in env:
                return env[name]
            val = scope.get(name)
            if val is None:
                raise RuntimeError(
                    "variable %r is not initialized; run the startup "
                    "program first" % name)
            if isinstance(val, np.ndarray):
                # a host array someone put into the scope (a restored
                # checkpoint): placed once, committed like a feed and
                # like every segment's outputs, so that the step lowers
                # to the program an uninterrupted run compiled
                val = jax.device_put(val, executor.place.device())
                scope.set(name, val)
            return val

        rng_state = scope.get(RNG_STATE_NAME)
        if rng_state is None:
            # committed placement, like the jit-returned key that will
            # replace it: an uncommitted first key makes every jitted
            # segment retrace (and recompile) on its second run
            rng_state = jax.device_put(
                jax.random.PRNGKey(self.program.random_seed or 0),
                executor.place.device())
            scope.set_local(RNG_STATE_NAME, rng_state)

        for i, seg in enumerate(self._plan):
            with obs_trace.span("executor/segment", cat="executor",
                                index=i, segment=seg["label"],
                                jit=seg["jit"] and not eager):
                rng_state = self._run_segment(i, seg, scope, env,
                                              resolve, rng_state, eager)
        scope.set(RNG_STATE_NAME, rng_state)

        # fetches not written this run (parameters, accumulated state)
        # resolve from the scope, matching the reference's
        # GetFetchVariable-on-scope semantics
        return [env[n] if n in env else scope.get(n)
                for n in self.fetch_names]

    def _run_segment(self, i, seg, scope, env, resolve, rng_state, eager):
        """One segment: resolve what it reads, run it (one jitted call,
        or op by op), put its outputs into `env` and its persistables
        back into the scope.  Returns the advanced rng state."""
        in_vals = {n: resolve(n) for n in seg["reads"] if n in env
                   or scope.has_var(n)}
        if seg["jit"] and not eager:
            out_vals, rng_state = self._run_jit_segment(
                i, seg, in_vals, rng_state)
        else:
            executor = self.executor
            ctx = ExecContext(executor, self.program, self.block_idx,
                              dict(in_vals), rng=rng_state, scope=scope,
                              place=executor.place)
            for od in seg["ops"]:
                # per-op attribution like the reference interpreter
                # (reference: executor.cc:126-127 RecordEvent per op,
                # executor.cc:29+66-77 FLAGS_check_nan_inf scan);
                # record_event is span-backed: rows land in the
                # profiler table AND on the obs trace timeline
                with profiler_mod.record_event(od.type):
                    outs = apply_op(ctx, od)
                if flags.get_flag("check_nan_inf"):
                    try:
                        _check_outputs_finite(od, outs)
                    except NonfiniteError as err:
                        # annotate the block-wide op position (error
                        # path only; list.index is identity-based)
                        try:
                            err.op_index = self.program.desc.block(
                                self.block_idx).ops.index(od)
                        except ValueError:
                            pass
                        raise
            rng_state = ctx.rng
            out_vals = {n: ctx.env[n] for n in seg["outputs"]
                        if n in ctx.env}
        env.update(out_vals)
        for n in seg["persist_writes"]:
            if n in out_vals:
                scope.set(n, out_vals[n])
        return rng_state

    @staticmethod
    def _segment_label(i, ops):
        """Stable display name: index + op-type span + op count.  Made
        once, when the plan is built (`seg["label"]`)."""
        span = ops[0].type if len(ops) == 1 else "%s..%s" % (
            ops[0].type, ops[-1].type)
        return "jit_segment[%d:%s x%d]" % (i, span, len(ops))

    def _run_jit_segment(self, i, seg, in_vals, rng_state):
        first_call = i not in self._jit_cache
        jitted = self._jit_cache.get(i)
        if jitted is None:
            obs_trace.instant("jit_build", cat="compile",
                              segment=seg["label"])
            ops = seg["ops"]
            out_names = tuple(seg["outputs"])
            program = self.program
            block_idx = self.block_idx
            executor = self.executor
            mutated = tuple(n for n in seg["outputs"] if n in seg["reads"])
            dn = self._donation
            if dn["mode"] == "off":
                mutated = ()
            elif dn["mode"] == "auto" and dn["widened"] \
                    and i < len(dn["widened"]):
                # the A0xx analysis proved these reads dead after the
                # segment — donate them too (reads-membership re-check
                # keeps a stale plan from widening past the signature)
                mutated += tuple(n for n in dn["widened"][i]
                                 if n not in mutated
                                 and n in seg["reads"])

            def segment_fn(mut_ins, ro_ins, rng):
                env = dict(ro_ins)
                env.update(mut_ins)
                ctx = ExecContext(executor, program, block_idx, env, rng=rng)
                for od in ops:
                    apply_op(ctx, od)
                outs = {n: env[n] for n in out_names if n in env}
                return outs, ctx.rng

            jitted = {
                "fn": jax.jit(segment_fn, donate_argnums=(0,)),
                "mutated": mutated,
            }
            self._jit_cache[i] = jitted
            if flags.get_flag("xla_cost_attribution") \
                    or obs_health.attribution_forced():
                # the static half of the memory drift join: the
                # segment's liveness activation peak, registered once
                # per build under the same attribution gate whose
                # publish_compile_stats call supplies the XLA half
                try:
                    obs_mem.register_segment_static(
                        seg["label"], ops,
                        seg["outputs"],
                        program.desc.block(block_idx))
                except Exception:
                    _log.debug("mem static registration failed for "
                               "segment %d", i, exc_info=True)

        mutated = jitted["mutated"]
        mut_ins = {n: v for n, v in in_vals.items() if n in mutated}
        ro_ins = {n: v for n, v in in_vals.items() if n not in mutated}
        label = seg["label"]
        profiled = profiler_mod.is_enabled()

        # cost attribution on the plain jit path
        # (FLAGS_xla_cost_attribution / health.force_attribution):
        # jax's AOT artifacts don't share the jit call path's
        # executable cache, so the old capture (`fn.lower().compile()`
        # AFTER the jit call already compiled) paid a second,
        # throwaway XLA compile per segment.  Instead, when
        # attribution is wanted the first build goes THROUGH an AOT
        # artifact — one compile that is both published and executed —
        # and once a segment holds attribution artifacts they keep
        # serving their signatures even after the flag drops (serving
        # warmup under force_attribution must not recompile on the
        # first real request).
        size_fn = getattr(jitted["fn"], "_cache_size", lambda: None)
        want_attr = (flags.get_flag("xla_cost_attribution")
                     or obs_health.attribution_forced())
        attr = jitted.get("attr_aot")
        has_live_attr = attr and any(v is not False
                                     for v in attr.values())
        if want_attr or has_live_attr:
            # only build NEW attribution artifacts for fresh segment
            # builds (first build, or a segment the jit call path
            # never compiled): flipping the flag on a live process
            # must not stall steady-state steps with inline recompiles
            # of already-warm signatures (the old _capture_xla_cost
            # also captured first builds only)
            allow_compile = want_attr and (
                first_call or not (size_fn() or 0))
            res = self._run_attr_aot(i, seg, jitted, mut_ins, ro_ins,
                                     rng_state, allow_compile,
                                     profiled)
            if res is not None:
                return res

        # dispatch async, traced or not: the host runs ahead of the
        # device, and device time is the device trace's to tell.  Compile
        # detection stays on (a retrace is the single costliest event,
        # telemetry must see it even unprofiled) - _cache_size is a
        # cheap int read.  Only fluid.profiler's table blocks on the
        # segment's outputs, so that a row is wall time and not just the
        # dispatch (the reference's RecordEvent/ParseEvents for the
        # compiled path; per-op rows come from eager mode); a trace hit
        # (new shapes/dtypes) lands in the /first(trace) row.
        pre_traces = size_fn()
        t0 = time.perf_counter()
        with obs_trace.span("executor/dispatch", cat="executor"):
            outs, rng = jitted["fn"](mut_ins, ro_ins, rng_state)
        post_traces = size_fn()
        traced = first_call or (pre_traces is not None
                                and post_traces is not None
                                and post_traces > pre_traces)
        if traced:
            self.traces += 1
            obs_tele.on_jit_trace(label)
        if profiled:
            jax.block_until_ready((outs, rng))
            profiler_mod.record(
                label + ("/first(trace)" if traced else ""),
                time.perf_counter() - t0)
        return outs, rng

    def _run_attr_aot(self, i, seg, jitted, mut_ins, ro_ins, rng_state,
                      allow_compile, profiled):
        """Attribution on the plain jit path, without the historical
        double compile: per (segment, signature) the FIRST build is
        `fn.lower().compile()` — the memory/cost analyses are
        published from that artifact AND the artifact executes the
        step, so attribution costs zero extra XLA compiles.  (Written
        when a jit call after `lower().compile()` compiled again; at
        jax 0.9.0 it does not — the two share the executable, checked
        on the CPU backend — so executing the artifact is a habit now,
        not a saving: ROADMAP Design 4.)  Returns (outs, rng), or None
        to fall back to the jit call path: an unknown signature with
        `allow_compile` off (post-warmup retraces, and signatures
        already warm in the jit cache, compile through the normal jit
        path), a failed lowering, or a signature quarantined by an
        execute failure."""
        attr = jitted.setdefault("attr_aot", {})
        try:
            sig = _values_signature_key(
                list(mut_ins.items()) + list(ro_ins.items())
                + [("@rng", rng_state)])
        except Exception:
            return None
        aot = attr.get(sig)
        if aot is False:
            return None
        label = seg["label"]
        if aot is None:
            if not allow_compile:
                return None
            try:
                compiled = jitted["fn"].lower(
                    mut_ins, ro_ins, rng_state).compile()
            except Exception:
                attr[sig] = False
                return None  # jit path reports its own trace error
            # a real XLA compile: telemetry must see it, exactly like
            # a jit-call-path trace would have been counted
            self.traces += 1
            obs_tele.on_jit_trace(label)
            obs_health.publish_compile_stats(label, compiled)
            attr[sig] = aot = compiled
        try:
            return self._exec_aot(aot, label, mut_ins, ro_ins,
                                  rng_state, profiled)
        except Exception as exc:
            # quarantine THIS signature and keep running — unless
            # dispatch already donated (deleted) the mutable inputs,
            # where a re-run would only mask the real error
            attr[sig] = False
            if any(getattr(v, "is_deleted", lambda: False)()
                   for v in mut_ins.values()):
                raise
            _log.warning("cost-attribution executable for %s failed "
                         "(%r); falling back to jit path", label, exc)
            return None

    @staticmethod
    def _exec_aot(aot, label, mut_ins, ro_ins, rng_state, profiled):
        """Dispatch one AOT artifact under the jit path's timing
        contract: async, and blocked with a profiler row only while
        fluid.profiler is on.  Raises on failure - the caller owns
        quarantine."""
        t0 = time.perf_counter()
        with obs_trace.span("executor/dispatch", cat="executor"):
            outs, rng = aot(mut_ins, ro_ins, rng_state)
        if profiled:
            jax.block_until_ready((outs, rng))
            profiler_mod.record(label, time.perf_counter() - t0)
        return outs, rng


# ---------------------------------------------------------------------------
# Executor
# ---------------------------------------------------------------------------

def guard_int64_narrowing(arr, name="feed"):
    """int64 host arrays execute as int32 (JAX x64 disabled).  Make the
    narrowing LOUD when it would actually wrap — embedding/beam ids
    beyond 2^31 would silently corrupt lookups otherwise.  Used by the
    executor feed path; reader.device_prefetch sidesteps the issue by
    keeping int64 feeds on host (see reader/prefetch.py)."""
    if getattr(arr, "dtype", None) == np.int64 and arr.size \
            and (arr.max() > np.iinfo(np.int32).max
                 or arr.min() < np.iinfo(np.int32).min):
        raise OverflowError(
            "feed %r: int64 values exceed int32 range (JAX x64 is "
            "disabled); ids must stay below 2^31" % name)


class Executor:
    """reference: python/paddle/v2/fluid/executor.py:149 + executor.cc:79."""

    _CACHE_MAX = 64

    def __init__(self, place=None):
        if isinstance(place, (list, tuple)):
            place = place[0]
        self.place = place or TPUPlace(0)
        # LRU-bounded: per-call Programs (evaluator eval/reset) would
        # otherwise grow this without bound
        from collections import OrderedDict

        self._cache = OrderedDict()
        # (program token, version) pairs that passed verification
        # under FLAGS_verify_program (see _verify_program)
        self._verified = set()

    def run(self, program=None, feed=None, fetch_list=None,
            feed_var_name="feed", fetch_var_name="fetch", scope=None,
            return_numpy=True, use_program_cache=True, eager=False):
        if program is None:
            program = framework.default_main_program()
        feed = feed or {}
        fetch_list = fetch_list or []
        scope = scope or global_scope()

        fetch_names = [f.name if isinstance(f, framework.Variable) else str(f)
                       for f in fetch_list]

        obs_tele.on_executor_run()
        # chaos hook: injected transient IOError/latency on the run
        # dispatch path (one None check when no fault plan is active)
        faults_mod.check("executor/run")
        run_span = obs_trace.span("executor/run", cat="executor",
                                  feeds=len(feed),
                                  fetches=len(fetch_names))
        try:
            return self._run_traced(run_span, program, feed, fetch_names,
                                    scope, return_numpy,
                                    use_program_cache, eager)
        except Exception as exc:
            # flight-recorder hook: a crashing run leaves a post-mortem
            # bundle (no-op unless obs.flight.install() was called).
            # An OOM-class failure (device RESOURCE_EXHAUSTED or the
            # mem_budget_gb pre-flight) additionally carries the static
            # timeline's top blamed buffers + the last mem_* gauges —
            # oom_context is {} for everything else.
            obs_flight.on_crash(
                exc, origin="executor/run",
                feeds=obs_flight.describe_feeds(feed),
                fetches=list(fetch_names), eager=bool(eager),
                **obs_mem.oom_context(exc, program, fetch_names))
            raise

    def _run_traced(self, run_span, program, feed, fetch_names, scope,
                    return_numpy, use_program_cache, eager):
        with run_span:
            t_run = time.perf_counter()
            feed_env = {}
            block0 = program.desc.block(0)
            if feed:
                with obs_trace.span("executor/feed", cat="executor"):
                    for name, val in feed.items():
                        feed_env[name] = self._prepare_feed(block0, name,
                                                            val)

            with obs_trace.span("executor/plan",
                                cat="executor") as plan_span:
                compiled, miss = self._compiled_for(
                    program, feed_env, fetch_names, use_program_cache)
                plan_span.set(miss=miss)
            traces = compiled.traces

            results = compiled.run(scope, feed_env, eager=eager)

            if return_numpy:
                with obs_trace.span("executor/fetch", cat="executor"):
                    results = [self._to_numpy(r) for r in results]
            if miss or compiled.traces != traces:
                # a run that built its plan or traced a segment is a
                # part of start-up; one that did neither leaves nothing
                obs_trace.emit_span(
                    "startup/executor_first_run", t_run,
                    time.perf_counter() - t_run, cat=obs_trace.STARTUP,
                    args={"place": type(self.place).__name__,
                          "ops": len(block0.ops), "plan_miss": int(miss),
                          "traces": compiled.traces - traces})
            return results

    def _compiled_for(self, program, feed_env, fetch_names,
                      use_program_cache):
        """(the `_CompiledProgram` for this call, whether it had to be
        built): the cache key, the lookup and, on a miss, verification,
        the memory pre-flight and the plan itself."""
        # dtype policy is trace-time state: a flipped amp flag must
        # not reuse executables built under the old policy
        key = (program._cache_token, program.version, 0,
               tuple(sorted(feed_env.keys())), tuple(fetch_names),
               flags.get_flag("amp_bf16"),
               flags.get_flag("amp_bf16_act"),
               flags.get_flag("bn_shifted_stats"),
               flags.get_flag("donation"))
        compiled = self._cache.get(key) if use_program_cache else None
        if compiled is not None:
            self._cache.move_to_end(key)
            return compiled, False
        with obs_trace.span("startup/executor_plan", cat=obs_trace.STARTUP,
                            ops=len(program.desc.block(0).ops)) as planned:
            # verify-before-first-compile (FLAGS_verify_program):
            # a malformed program fails HERE with a Diagnostic-
            # derived error naming op index + var, not three
            # layers down as an XLA trace error
            if flags.get_flag("verify_program"):
                self._verify_program(program, fetch_names)
            # OOM pre-flight (FLAGS_mem_budget_gb): refuse a
            # program whose static peak busts the budget BEFORE
            # any compile.  The MemoryBudgetError routes through the
            # same OOM flight-bundle path a device
            # RESOURCE_EXHAUSTED does.
            budget = flags.get_flag("mem_budget_gb")
            if budget:
                obs_mem.preflight(program, fetch_names, budget)
            compiled = _CompiledProgram(self, program, 0,
                                        sorted(feed_env.keys()),
                                        fetch_names)
            if use_program_cache:
                self._cache[key] = compiled
                while len(self._cache) > self._CACHE_MAX:
                    ekey, evicted = self._cache.popitem(last=False)
                    # name the victim: a hot serving mix thrashing
                    # the program cache looks like random recompiles
                    self._retire_segment_gauges(evicted)
                    _log.debug(
                        "evicted program cache entry: token=%s "
                        "version=%s feeds=%s fetches=%s",
                        ekey[0], ekey[1], ekey[3], ekey[4])
            planned.set(segments=len(compiled._plan))
        return compiled, True

    def _retire_segment_gauges(self, evicted):
        """Per-segment gauges (`xla_*`/`mem_*{segment=}`) are
        published at build time but were never RETIRED when the LRU
        evicted their program — a long-lived serving process slowly
        accumulated dead segment labels in /metrics.  Drop the
        evicted program's labels through the registry's `remove()`
        path — EXCEPT labels a still-cached program shares (labels
        are shape-independent, so a structurally identical warm
        program would never re-publish the removed child and its
        live metrics would silently vanish for the process
        lifetime)."""
        try:
            labels = {seg["label"] for seg in evicted._plan}
            for other in self._cache.values():
                labels.difference_update(
                    seg["label"] for seg in other._plan)
            if labels:
                obs_health.retire_compile_stats(labels)
                obs_mem.retire_segments(labels)
        except Exception:
            _log.debug("segment gauge retirement failed",
                       exc_info=True)

    def _verify_program(self, program, fetch_names):
        """FLAGS_verify_program path: full analysis once per (program
        identity, version) — edits bump the version, re-verifying; a
        clean verdict is cached so steady-state runs pay one set
        lookup."""
        vkey = (program._cache_token, program.version)
        if vkey in self._verified:
            return
        from .. import analysis

        analysis.check_program(
            program, level="full", fetches=list(fetch_names),
            origin="executor").raise_on_error()
        self._verified.add(vkey)
        if len(self._verified) > 4 * self._CACHE_MAX:
            self._verified.clear()  # rare: unbounded program churn

    def _prepare_feed(self, block_desc, name, val):
        if isinstance(val, (RaggedTensor, SelectedRows)):
            return val
        if isinstance(val, (list, tuple)) and any(
                isinstance(v, (RaggedTensor, SelectedRows))
                for v in val):
            # host array-of-tensors feed (e.g. beam_search_decode steps)
            return list(val)
        vd = block_desc.vars.get(name)
        if isinstance(val, jax.Array):
            # pre-placed feed (reader.device_prefetch): keep it on
            # device — no host round-trip; the int64 guard already ran
            # before the worker-thread device_put
            target = (np_dtype(vd.dtype) if vd is not None
                      and vd.dtype is not None else None)
            if target is not None and val.dtype != target \
                    and target != np.dtype(np.int64):
                val = val.astype(target)
            return jax.device_put(val, self.place.device())
        arr = np.asarray(val)
        # int64 feeds execute as int32 (JAX x64 disabled): when the
        # target dtype actually narrows to int32, check the range
        # BEFORE the astype so overflow is LOUD instead of silently
        # wrapping ids (embedding/beam ids beyond 2^31 would corrupt
        # lookups).  Feeds into float vars keep casting as before.
        target = (np_dtype(vd.dtype) if vd is not None
                  and vd.dtype is not None else np.dtype(np.int32))
        if target == np.int32:
            guard_int64_narrowing(arr, name)
        if vd is not None and vd.dtype is not None:
            arr = arr.astype(np_dtype(vd.dtype), copy=False)
        elif arr.dtype == np.int64:
            arr = arr.astype(np.int32)
        # host->device feed cost, made visible instead of inferred from
        # step-time noise (pre-placed jax.Array feeds above moved
        # nothing and are not counted)
        obs_tele.on_transfer("h2d", arr.nbytes)
        return jax.device_put(arr, self.place.device())

    @staticmethod
    def _to_numpy(r):
        if r is None:
            return None
        if isinstance(r, RaggedTensor):
            if r.values.dtype == jnp.bfloat16:
                r = r.with_values(r.values.astype(jnp.float32))
            return r
        if isinstance(r, jax.Array):
            obs_tele.on_transfer("d2h", r.size * r.dtype.itemsize)
        arr = np.asarray(r)
        if arr.dtype == jnp.bfloat16:
            # bf16 is an internal compute dtype (FLAGS_amp_bf16_act);
            # the feed/fetch contract stays f32
            arr = arr.astype(np.float32)
        return arr
