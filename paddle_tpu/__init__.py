"""paddle_tpu — a TPU-native deep-learning framework with the capability
surface of the reference (alphagh/Paddle: PaddlePaddle v2 + Fluid).

Top-level namespace mirrors the reference's `paddle.v2` entry points
(batch, reader, dataset) with `paddle_tpu.fluid` as the program-based API.
Compute lowers to JAX/XLA: whole train steps compile to single TPU
executables; parallelism is expressed as jax.sharding meshes (see
paddle_tpu.parallel).
"""

from . import reader
from . import dataset
from .reader.decorator import batch

__version__ = "0.1.0"

__all__ = ["reader", "dataset", "batch", "fluid", "v2", "infer",
           "layer", "image", "obs", "resilience", "analysis",
           "compile"]

from . import analysis  # noqa: E402
from . import compile  # noqa: E402,A004 — paddle_tpu.compile subsystem
from . import obs  # noqa: E402
from . import resilience  # noqa: E402
from . import fluid  # noqa: E402
from . import v2  # noqa: E402
from .v2 import layer  # noqa: E402
from .v2 import image  # noqa: E402
from .v2.inference import infer  # noqa: E402
