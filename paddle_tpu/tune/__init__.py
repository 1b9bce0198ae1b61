"""paddle_tpu.tune — the offline autotuning autopilot (ROADMAP item 3).

Answers "what config do I launch this model with on N chips" without
burning a pod slice on the question.  Three stages, each riding a
subsystem an earlier PR built:

  * `space`   — declarative search space over mesh shape x pass
    pipeline x batch x micro-batch, with per-knob constraints so
    invalid points are never enumerated.
  * `rank`    — static scoring with ZERO devices: the PR 6 sharding
    analyzer rejects S001–S005-erroring candidates, the costmodel
    prices their wire bytes, the roofline floors predict their step
    time, and the per-device HBM estimate enforces the budget.
  * `fit`     — a least-squares per-term correction of predicted vs
    measured step time over the tagged records (leg `ptune:<tag>` +
    a `"config"` blob) of a `perf_history.jsonl`, so the ranking
    improves with every measured run (the TVM loop, PAPERS.md).

Operator surface: `python -m paddle_tpu.tools.tune_cli` ("ptune")
with plan / fit / report / --selftest; docs/TUNING.md has
the grammar, the ranking formula, and the calibration workflow.
"""

from . import space
from . import rank
from . import fit
from . import models
from .space import Candidate, SearchSpace, mesh_shapes_for
from .rank import Calibration, RankedPlan, rank as rank_candidates
from .fit import fit_calibration, join_history

__all__ = ["space", "rank", "fit", "models",
           "Candidate", "SearchSpace", "mesh_shapes_for",
           "Calibration", "RankedPlan", "rank_candidates",
           "fit_calibration", "join_history"]
