"""paddle_tpu.spmd — the multi-chip SPMD training mainline.

Promotes the `parallel/` prototypes into a first-class subsystem
(ROADMAP item 1; reference: the C++/Go pserver + MultiGradientMachine
distributed stack the whole 2018 design existed for):

  * `plan`       — rule-driven partition planning: regex partition
                   rules layered over the `sharding.param_spec`
                   heuristics, producing a serializable plan artifact
                   (`pshard plan`) the S001 analyzer and the trainer
                   both consume.
  * `trainer`    — `SpmdTrainer`: the pjit/NamedSharding lowering of
                   the fluid train step, with zero1 optimizer-state
                   sharding and optional bucketed ring-allreduce
                   gradient overlap.
  * `overlap`    — the explicit data-parallel step: forward+backward
                   per device shard inside shard_map, gradients
                   ring-reduced in buckets overlapping the backward.
  * `checkpoint` — sharded per-host checkpoints (host-local shard
                   files + manifests) that restore WITHOUT densifying,
                   composing with the resilience supervisor for
                   preempt/auto-resume.
"""

from .plan import (PartitionPlan, build_partition_plan,
                   match_partition_rules, load_rules)
from .trainer import SpmdTrainer, attach_supervisor
from .checkpoint import (SpmdCheckpointSaver, save_sharded,
                         restore_sharded, latest_sharded_checkpoint,
                         StaleGenerationError,
                         measure_densify_restore)
from .overlap import make_overlapped_dp_step, overlap_supported

__all__ = [
    "PartitionPlan", "build_partition_plan", "match_partition_rules",
    "load_rules", "SpmdTrainer", "attach_supervisor",
    "SpmdCheckpointSaver", "save_sharded", "restore_sharded",
    "latest_sharded_checkpoint", "StaleGenerationError",
    "measure_densify_restore", "make_overlapped_dp_step",
    "overlap_supported",
]
