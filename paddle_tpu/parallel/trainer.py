"""Mesh-parallel training driver.

The reference's single-process multi-device trainer splits the batch,
runs per-device threads, and ring-reduces gradients
(reference: MultiGradientMachine.h:44-83, parallel_do_op.cc:112).  Here
the whole train step (forward + backward + optimizer, one Program block)
is ONE jitted function laid out over the mesh: batch sharded on dp,
weights sharded on mp, gradients all-reduced by XLA over ICI.
"""

import time

import numpy as np

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..jit import FunctionalProgram, state_from_scope
from ..obs import flight as obs_flight
from ..obs import health as obs_health
from ..obs import telemetry as obs_tele
from ..obs import trace as obs_trace
from ..utils import flags as _flags
from .sharding import (param_spec, batch_spec, is_optimizer_state,
                       optimizer_state_names, zero1_spec)

__all__ = ["make_parallel_step", "ParallelTrainer", "verify_sharding"]


def verify_sharding(program, mesh, feed_names, fetch_names,
                    feed_specs=None, zero_stage=0, dp_axis="dp",
                    mp_axis="mp", origin="parallel_trainer",
                    hbm_gb=None):
    """Run the static SPMD analyzer over `program` against `mesh` and
    raise ProgramVerificationError on any error-severity S0xx finding
    (non-divisible shard, schedule hazard, budget overrun) — BEFORE
    anything lowers or compiles.  The trust-boundary gate behind
    FLAGS_verify_sharding; callers can also invoke it directly.
    Returns the ShardingPlan for introspection."""
    from ..analysis import shard as shard_analysis

    plan = shard_analysis.analyze_sharding(
        program, mesh, feed_names=list(feed_names),
        feed_specs=feed_specs, fetches=list(fetch_names),
        zero_stage=zero_stage, dp_axis=dp_axis, mp_axis=mp_axis,
        hbm_gb=hbm_gb, publish=True, origin=origin,
        # trainer feeds carry their real runtime shapes: a
        # non-divisible static batch is a hard S002 here
        concrete_feeds=True)
    plan.report.raise_on_error()
    return plan


def make_parallel_step(program, feed_names, fetch_names, mesh,
                       state_template, dp_axis="dp", mp_axis="mp",
                       donate_state=None, fp=None, zero_stage=0,
                       feed_specs=None, spec_overrides=None):
    """Compile a Program block into a sharded step function.

    donate_state: None (default) routes through the donation plan —
    FLAGS_donation=off disables state donation, any other mode keeps
    it (analysis.state_donation); pass an explicit bool to override
    (the AOT "-nodonate" twin and obs.comm's compute-only twin do).

    Returns (step, state_shardings) where
      step(state, feeds, rng) -> (fetches, new_state)
    is jitted with: state sharded per param_spec, feeds sharded on dp,
    fetches replicated (losses/metrics are scalars after mean).

    zero_stage=1 additionally shards the optimizer accumulators
    (velocity/moment/... vars) over dp — ZeRO-1: GSPMD turns the
    gradient all-reduce into reduce-scatter + all-gather and each chip
    keeps 1/dp of the optimizer state.

    feed_specs overrides the default dp batch sharding per feed name
    (e.g. {"tokens": P("dp", "sp")} lays the sequence dim over the sp
    axis for sequence-parallel programs).

    spec_overrides overrides the heuristic `param_spec` per STATE var
    name — the spmd partition-plan hook (spmd/plan.py): a plan entry
    carries the final layout (zero1 already applied by the analyzer),
    so an overridden name bypasses both the heuristic and the zero1
    rewrite here.

    With FLAGS_verify_sharding on, the static SPMD analyzer runs over
    the program/mesh pair first (unless the caller already did —
    ParallelTrainer.init verifies before running startup) and rejects
    S0xx errors before any lowering.
    """
    if donate_state is None:
        from ..analysis.alias import state_donation

        donate_state = state_donation()
    if fp is None:
        if program is not None and _flags.get_flag("verify_sharding"):
            verify_sharding(program, mesh, feed_names, fetch_names,
                            feed_specs=feed_specs,
                            zero_stage=zero_stage, dp_axis=dp_axis,
                            mp_axis=mp_axis, origin="parallel_step")
        fp = FunctionalProgram(program, feed_names, fetch_names)

    # exact accumulator names from the program's optimizer ops (the
    # name-suffix regex stays only for detached state dicts)
    acc_names = optimizer_state_names(program) if program is not None \
        else None

    spec_overrides = spec_overrides or {}

    def spec_for(name, shape):
        if name in spec_overrides:
            return spec_overrides[name]
        spec = param_spec(name, shape, mesh, mp_axis=mp_axis)
        if zero_stage >= 1 and is_optimizer_state(name, known=acc_names):
            spec = zero1_spec(spec, shape, mesh, dp_axis=dp_axis)
        return spec

    state_shardings = {
        name: NamedSharding(mesh, spec_for(name, v.shape))
        for name, v in state_template.items()
    }

    feed_specs = feed_specs or {}

    def step(state, feeds, rng):
        feeds = {
            n: jax.lax.with_sharding_constraint(
                v, NamedSharding(mesh, feed_specs.get(
                    n, batch_spec(v.shape, mesh, dp_axis))))
            if hasattr(v, "shape") else v
            for n, v in feeds.items()
        }
        fetches, new_state = fp(state, feeds, rng)
        return fetches, new_state

    jitted = jax.jit(
        step,
        in_shardings=(state_shardings, None, None),
        out_shardings=(None, state_shardings),
        donate_argnums=(0,) if donate_state else (),
    )
    return jitted, state_shardings


class ParallelTrainer:
    """End-to-end sharded trainer for a built Program.

    Usage:
        trainer = ParallelTrainer(main_prog, startup_prog,
                                  feed_names=["image", "label"],
                                  fetch_names=[loss.name], mesh=mesh)
        trainer.init()                       # run startup, shard params
        (loss,) = trainer.step({"image": x, "label": y})

    `step()` keeps one step in flight: it dispatches step N, waits for
    step N-1 and returns step N's fetches as `jax.Array`s that may
    still be pending, so the host prepares the next step while the
    device runs this one.  Reading a fetch on the host waits for it;
    the state is whole once `jax.block_until_ready(trainer.state)` or
    any host read of it (`fetch_state`, `dump_state_to`, a checkpoint)
    returns.  With the numerics monitor, the flight recorder or a
    telemetry step observer on, which need a step's values on the host
    inside that step, `step()` waits for its own fetches instead.
    """

    def __init__(self, main_program, startup_program, feed_names,
                 fetch_names, mesh, dp_axis="dp", mp_axis="mp", seed=0,
                 zero_stage=0, feed_specs=None):
        self.main_program = main_program
        self.startup_program = startup_program
        self.feed_names = list(feed_names)
        self.fetch_names = list(fetch_names)
        self.mesh = mesh
        self.dp_axis = dp_axis
        self.mp_axis = mp_axis
        self.zero_stage = zero_stage
        self.feed_specs = feed_specs
        self._base_rng = jax.random.PRNGKey(seed)
        self._step_count = 0
        self._step_fn = None
        # the step function's own count of the signatures it has traced
        # (`init` finds it; `int` where a step function has none: always
        # 0) and what it read last: a step that raised the count was a
        # first step
        self._traced, self._traces = int, 0
        self._monitor = None
        self.state = None

    @property
    def state(self):
        return self._state

    @state.setter
    def state(self, value):
        # whoever hands the trainer a state (init, a restore) starts it
        # afresh: the step in flight ran on the state this replaces,
        # and what it raised is not the next step's to report
        self._state = value
        self._in_flight = None

    def init(self, scope=None, executor=None):
        """Run the startup program (single device), then lay the state out
        over the mesh per the sharding specs.

        With FLAGS_verify_sharding on, the static SPMD analyzer runs
        FIRST — before the startup program executes, before any jit
        trace — so a non-divisible shard or schedule hazard rejects
        with op/var/spec identity instead of burning an XLA compile."""
        with obs_trace.span("startup/trainer_init", cat=obs_trace.STARTUP,
                            trainer=type(self).__name__):
            self._init(scope, executor)
        return self

    def _init(self, scope, executor):
        from ..fluid.executor import Executor, CPUPlace
        from ..core.scope import Scope

        self._verify()

        scope = scope or Scope()
        exe = executor or Executor(CPUPlace())
        exe.run(self.startup_program, scope=scope)

        # numerics health: when enabled, the monitor's on-device
        # reductions (nonfinite counts over fetches + grads, global
        # grad norm) join the jitted step as extra replicated fetches —
        # XLA folds the cross-chip reduce into the step executable
        fetch_all = list(self.fetch_names)
        self._monitor = None
        if obs_health.enabled():
            self._monitor = obs_health.NumericsMonitor(
                self.main_program,
                tensors=list(self.fetch_names)).install()
            fetch_all += self._monitor.fetch_names

        fp = FunctionalProgram(self.main_program, self.feed_names,
                               fetch_all)
        state = state_from_scope(fp, scope)
        self._step_fn, self._shardings = self._make_step(fp, state,
                                                         fetch_all)
        self._traced = getattr(self._step_fn, "_cache_size", int)
        self._traces = self._traced()
        # place state on the mesh
        with obs_trace.span("startup/state_place", cat=obs_trace.STARTUP,
                            arrays=len(state),
                            bytes=sum(v.nbytes for v in state.values())):
            self.state = {
                n: jax.device_put(np.asarray(v), self._shardings[n])
                for n, v in state.items()
            }

    def _verify(self):
        """The pre-startup trust-boundary gate; `SpmdTrainer` replaces
        it with the partition-plan build (which raises on the same
        S0xx errors, rules included)."""
        if _flags.get_flag("verify_sharding"):
            verify_sharding(self.main_program, self.mesh,
                            self.feed_names, self.fetch_names,
                            feed_specs=self.feed_specs,
                            zero_stage=self.zero_stage,
                            dp_axis=self.dp_axis, mp_axis=self.mp_axis,
                            origin="parallel_trainer")

    def _make_step(self, fp, state, fetch_all):
        """Build (step_fn, state_shardings) — the lowering hook
        subclasses override (SpmdTrainer routes plan specs and the
        overlapped-dp schedule through here)."""
        return make_parallel_step(
            self.main_program, self.feed_names, fetch_all,
            self.mesh, state, dp_axis=self.dp_axis, mp_axis=self.mp_axis,
            fp=fp, zero_stage=self.zero_stage, feed_specs=self.feed_specs)

    def step(self, feeds):
        step_id = self._step_count
        self._step_count += 1
        # step telemetry into the unified registry inside a
        # parallel/step span, each part of the step a child span of its
        # own: in the profiler's trace they say what the host was doing
        # while the devices idled
        with obs_tele.step("parallel", step=step_id) as timer:
            with obs_trace.span("parallel/prepare", cat="trainer"):
                rng = jax.random.fold_in(self._base_rng, step_id)
                feeds = {n: jnp_asarray(v) for n, v in feeds.items()}
                timer.examples = next(
                    (int(v.shape[0]) for v in feeds.values()
                     if getattr(v, "ndim", 0)), None)
                described = obs_flight.describe_feeds(feeds)
            monitor = self._monitor
            recording = obs_flight.active()
            # whoever needs this step's values on the host inside this
            # step makes it wait for its own fetches; otherwise one
            # step stays in flight and the wait is for the one before
            own = monitor is not None or recording
            blamed = step_id, described
            try:
                # trace under the mesh context so mesh-aware op kernels
                # (ring flash_attention) see the sp topology
                with obs_trace.span("parallel/dispatch", cat="trainer"), \
                        jax.set_mesh(self.mesh):
                    fetches, self._state = self._step_fn(self._state,
                                                         feeds, rng)
                # bounded run-ahead: the device always has the next
                # step queued behind the one it runs, the host is never
                # more than one step ahead, and trainer_step_seconds
                # stays a step's worth of wall time (the dispatch of
                # this step and the rest of the last), never just the
                # async dispatch.  The fetches returned may be pending.
                this = step_id, described, fetches
                if own:
                    waited, self._in_flight = this, None
                else:
                    waited, self._in_flight = self._in_flight, this
                with obs_trace.span(
                        "parallel/wait", cat="trainer",
                        for_step=-1 if waited is None else waited[0],
                        own=int(own)):
                    if waited is not None:
                        blamed = waited[:2]
                        jax.block_until_ready(waited[2])
            except Exception as exc:
                obs_flight.on_crash(exc, origin="parallel/step",
                                    step=blamed[0], feeds=blamed[1])
                raise
            if self._traced() != self._traces:
                # a step that traced is a part of start-up; one that did
                # not leaves nothing
                self._traces = self._traced()
                obs_trace.emit_span(
                    "startup/trainer_first_step", timer.t0,
                    time.perf_counter() - timer.t0, cat=obs_trace.STARTUP,
                    args={"step": step_id})
            with obs_trace.span("parallel/record", cat="trainer"):
                timer.record()
                if monitor is not None:
                    n_user = len(self.fetch_names)
                    monitor.record(dict(zip(monitor.fetch_names,
                                            fetches[n_user:])))
                    fetches = fetches[:n_user]
                if recording:
                    loss = None
                    first = fetches[0] if fetches else None
                    if first is not None \
                            and getattr(first, "size", 0) == 1:
                        loss = float(np.asarray(first).reshape(-1)[0])
                    obs_flight.record_step("parallel", step_id,
                                           feeds=described, loss=loss)
        return fetches

    def fetch_state(self, name):
        return np.asarray(self.state[name])

    def sharding_plan(self, hbm_gb=None):
        """Introspection: the static SPMD analysis of this trainer's
        program/mesh pair (specs, replication reasons, comm cost,
        per-device peak-HBM estimate) WITHOUT raising — see
        docs/ANALYSIS.md 'lint before you burn a pod slice'."""
        from ..analysis import shard as shard_analysis

        return shard_analysis.analyze_sharding(
            self.main_program, self.mesh, feed_names=self.feed_names,
            feed_specs=self.feed_specs, fetches=self.fetch_names,
            zero_stage=self.zero_stage, dp_axis=self.dp_axis,
            mp_axis=self.mp_axis, hbm_gb=hbm_gb, publish=False)

    # -- supervisor integration ---------------------------------------------
    def dump_state_to(self, scope):
        """Host copies of the sharded state into `scope` (called by
        the resilience supervisor right before a checkpoint save)."""
        for name, val in self.state.items():
            scope.set(name, np.asarray(val))

    def load_state_from(self, scope):
        """Re-place checkpointed host values onto the mesh with the
        step function's shardings (after a supervisor restore)."""
        restored = {}
        for name in self.state:
            val = scope.get(name)
            if val is None:
                raise KeyError("checkpoint is missing state var %r"
                               % name)
            restored[name] = jax.device_put(np.asarray(val),
                                            self._shardings[name])
        self.state = restored


def jnp_asarray(v):
    import jax.numpy as jnp

    if isinstance(v, jax.Array):
        return v
    return jnp.asarray(np.asarray(v))
