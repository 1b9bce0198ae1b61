"""paddle_tpu.tune — the offline autotuning autopilot (ROADMAP item 3).

Answers "what config do I launch this model with on N chips" without
burning a pod slice on the question.  Four stages, each riding a
subsystem an earlier PR built:

  * `space`   — declarative search space over mesh shape x pass
    pipeline x batch x micro-batch, with per-knob constraints so
    invalid points are never enumerated.
  * `rank`    — static scoring with ZERO devices: the PR 6 sharding
    analyzer rejects S001–S005-erroring candidates, the costmodel
    prices their wire bytes, the roofline floors predict their step
    time, and the per-device HBM estimate enforces the budget.
  * `measure` — only the top-K survivors ever touch hardware, each
    through bench.py's normal AOT path, landing tagged
    records (leg `ptune:<tag>` + a `"config"` blob) in
    `perf_history.jsonl`.
  * `fit`     — a least-squares per-term correction of predicted vs
    measured step time over that history, so the ranking improves
    with every run (the TVM loop, PAPERS.md).

Operator surface: `python -m paddle_tpu.tools.tune_cli` ("ptune")
with plan / measure / fit / report / --selftest; docs/TUNING.md has
the grammar, the ranking formula, and the calibration workflow.
"""

from . import space
from . import rank
from . import measure
from . import fit
from . import models
from .space import Candidate, SearchSpace, mesh_shapes_for
from .rank import Calibration, RankedPlan, rank as rank_candidates
from .measure import measure_plan
from .fit import fit_calibration, join_history

__all__ = ["space", "rank", "measure", "fit", "models",
           "Candidate", "SearchSpace", "mesh_shapes_for",
           "Calibration", "RankedPlan", "rank_candidates",
           "measure_plan", "fit_calibration", "join_history"]
