"""SpmdTrainer — the multi-chip SPMD training mainline.

A thin subclass of `parallel.ParallelTrainer` that routes the lowering
through the partition-plan artifact (`plan.build_partition_plan`):

  * the plan build IS the pre-startup verification (it runs the static
    analyzer with the partition rules and raises on S0xx errors), so
    `_verify` defers to it instead of running the analyzer twice;
  * the plan's per-var specs override the `sharding.param_spec`
    heuristics in the fused GSPMD step, so a regex rule edit changes
    the compiled layout with no trainer code change;
  * with `bucket_bytes > 0` and a pure-dp layout, the step lowers to
    the explicit overlapped schedule (`overlap.make_overlapped_dp_step`)
    — gradients ring-reduce in buckets while the backward still runs —
    and falls back to the fused path otherwise
    (`overlap_fallback_reason` says why);
  * `attach_supervisor` wires the sharded per-host checkpoint saver
    into the resilience supervisor: preempt/resume round-trips WITHOUT
    densifying the (possibly zero1-sharded) optimizer state.
"""

import jax

from ..parallel.trainer import ParallelTrainer, make_parallel_step
from .overlap import (make_overlapped_dp_step, overlap_supported,
                      DEFAULT_BUCKET_BYTES)
from .plan import build_partition_plan, load_rules

__all__ = ["SpmdTrainer", "attach_supervisor"]


class SpmdTrainer(ParallelTrainer):
    """End-to-end plan-driven SPMD trainer.

    Usage::

        trainer = SpmdTrainer(main_prog, startup_prog,
                              feed_names=["image", "label"],
                              fetch_names=[loss.name], mesh=mesh,
                              rules=[(r"fc_.*\\.w_0", ("mp", None))],
                              zero_stage=1)
        trainer.init()
        (loss,) = trainer.step({"image": x, "label": y})
        trainer.save_checkpoint("ckpts", step=100)   # sharded per host

    rules: partition rules in any `plan.load_rules` shape (path, rule
        document, or [(pattern, spec), ...]); None keeps the pure
        heuristic layout.
    plan: a pre-built `PartitionPlan` (e.g. loaded from the `pshard
        plan` artifact) — skips the analyzer run; the plan's mesh axes
        must match `mesh`.
    bucket_bytes: > 0 requests the overlapped explicit-dp schedule
        with ring-allreduce buckets of that size; 0 (default) keeps
        the fused GSPMD step.  `step_mode` records which lowering ran.
    """

    def __init__(self, main_program, startup_program, feed_names,
                 fetch_names, mesh, rules=None, plan=None,
                 bucket_bytes=0, model=None, **kw):
        super().__init__(main_program, startup_program, feed_names,
                         fetch_names, mesh, **kw)
        self.rules = load_rules(rules) if rules is not None else None
        self.plan = plan
        self.bucket_bytes = int(bucket_bytes or 0)
        self.model = model
        self.step_mode = None
        self.overlap_fallback_reason = None
        self._fetch_all = list(fetch_names)
        # elastic membership identity: None = not elastic (no restore
        # guard); the elastic layer (resilience/elastic.py) sets the
        # committed view's generation here so checkpoints are stamped
        # and stale restores refused
        self.elastic_generation = None

    # -- plan-driven lowering hooks -----------------------------------------
    def _build_plan(self):
        return build_partition_plan(
            self.main_program, self.mesh, self.feed_names,
            self.fetch_names, rules=self.rules,
            zero_stage=self.zero_stage, feed_specs=self.feed_specs,
            dp_axis=self.dp_axis, mp_axis=self.mp_axis,
            model=self.model, raise_on_error=True)

    def _verify(self):
        # the plan build runs the analyzer (rules included) and raises
        # on the same S0xx errors verify_sharding would — one pass
        if self.plan is None:
            self.plan = self._build_plan()
        else:
            want = {a: int(s) for a, s in dict(self.mesh.shape).items()}
            if dict(self.plan.mesh_axes) != want:
                raise ValueError(
                    "partition plan was built for mesh %r but the "
                    "trainer mesh is %r — rebuild with `pshard plan`"
                    % (dict(self.plan.mesh_axes), want))
        # stamp this worker's identity into any future flight bundle:
        # a multi-host post-mortem must say WHICH process on WHICH
        # mesh (and against which plan) died, not just that one did
        from ..obs import fleet as obs_fleet
        from ..obs import flight as obs_flight

        obs_flight.set_host_context(
            host=obs_fleet.host_id(),
            process_index=int(jax.process_index()),
            mesh_axes={a: int(s)
                       for a, s in dict(self.mesh.shape).items()},
            plan_fingerprint=self.plan.fingerprint())

    def _make_step(self, fp, state, fetch_all):
        if self.plan is None:       # init() not used (tests drive
            self.plan = self._build_plan()  # _make_step directly)
        self._fetch_all = list(fetch_all)
        self._state_template = state
        if self.bucket_bytes > 0:
            ok, reason = overlap_supported(
                self.main_program, self.mesh, dp_axis=self.dp_axis,
                zero_stage=self.zero_stage)
            if ok:
                self.step_mode = "overlap-dp"
                return make_overlapped_dp_step(
                    self.main_program, self.feed_names, fetch_all,
                    self.mesh, state, dp_axis=self.dp_axis,
                    bucket_bytes=self.bucket_bytes,
                    feed_specs=self.feed_specs)
            self.overlap_fallback_reason = reason
        self.step_mode = "gspmd"
        overrides = {n: self.plan.spec_of(n) for n in state
                     if self.plan.has(n)}
        return make_parallel_step(
            self.main_program, self.feed_names, fetch_all, self.mesh,
            state, dp_axis=self.dp_axis, mp_axis=self.mp_axis, fp=fp,
            zero_stage=self.zero_stage, feed_specs=self.feed_specs,
            spec_overrides=overrides)

    # -- sharded checkpoints ------------------------------------------------
    def save_checkpoint(self, root, step):
        """Blocking sharded save: host-local shard files + manifest
        under root/checkpoint_<step>.  Use `attach_supervisor` /
        `SpmdCheckpointSaver` for the background-writing loop form."""
        from .checkpoint import SpmdCheckpointSaver

        saver = SpmdCheckpointSaver(self, root, interval_secs=0.0)
        snap = saver.save(step)
        saver.wait()
        return snap

    def restore_checkpoint(self, root, max_generation=None):
        """Restore the newest complete sharded snapshot under `root`
        into this trainer's shardings (shard-exact when the layout
        matches; densified reassembly only on a layout change).
        `max_generation` defaults to the trainer's elastic generation
        (when set) so a stale host refuses a newer manifest.
        Returns the restore info dict ({step, snap, generation,
        densified})."""
        from .checkpoint import (latest_sharded_checkpoint,
                                 restore_sharded)

        snap = latest_sharded_checkpoint(root)
        if snap is None:
            raise IOError("no complete sharded checkpoint under %r"
                          % str(root))
        if max_generation is None:
            max_generation = self.elastic_generation
        state, info = restore_sharded(snap, self._shardings,
                                      max_generation=max_generation)
        self.state = state
        return info


def attach_supervisor(trainer, ckpt_dir, interval_secs=30.0,
                      max_to_keep=3, **kw):
    """A resilience `TrainingSupervisor` whose checkpoints are the
    SHARDED per-host snapshots — preempt/auto-resume without ever
    densifying the optimizer state.

    The supervisor detects the saver's `latest`/`restore_latest`
    protocol and routes resume through them; `state_dump` stays None
    because `SpmdCheckpointSaver.save` captures the trainer's sharded
    state directly (no dense scope copy exists at any point).
    """
    from ..core.scope import Scope
    from ..resilience.supervisor import TrainingSupervisor
    from .checkpoint import SpmdCheckpointSaver

    if trainer.state is None:
        raise ValueError("call trainer.init() before attaching a "
                         "supervisor")
    saver = SpmdCheckpointSaver(trainer, ckpt_dir,
                                interval_secs=interval_secs,
                                max_to_keep=max_to_keep)
    kw.setdefault("generation",
                  getattr(trainer, "elastic_generation", None) or 0)
    return TrainingSupervisor(ckpt_dir, scope=Scope(), saver=saver,
                              **kw)
