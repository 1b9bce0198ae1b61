"""paddle_tpu.compile — Program-level IR rewrite passes.

`passes` + `opt_passes`: rewrite passes over the analysis subsystem's
def-use/liveness machinery: the cleanup set (dead-op/dead-var
elimination, shape/fill constant folding, pure-op CSE) plus the
cost-model-guided optimization passes (`layout` NCHW→NHWC gated on the
TPU-tiled roofline, `fuse` elementwise-chain fusion, `auto_remat`
budget-driven activation checkpointing — knobs like `fuse:cap=8` fold
into the pipeline id), run by a `PassManager` that re-verifies the IR
around every pass.  Gated by `FLAGS_compile_passes`.

Compiled executables persist through JAX's own compilation cache
(`utils/compile_cache.py`), not through anything here.
"""

from . import passes
from . import opt_passes
from .passes import PassManager, optimize_program

__all__ = ["passes", "opt_passes", "PassManager", "optimize_program"]
