"""paddle_tpu — a TPU-native deep-learning framework with the capability
surface of the reference (alphagh/Paddle: PaddlePaddle v2 + Fluid).

Top-level namespace mirrors the reference's `paddle.v2` entry points
(batch, reader, dataset) with `paddle_tpu.fluid` as the program-based API.
Compute lowers to JAX/XLA: whole train steps compile to single TPU
executables; parallelism is expressed as jax.sharding meshes (see
paddle_tpu.parallel).
"""

import time as _time

_IMPORT_BEGAN = _time.perf_counter()

# the tracer first: the package's import is the first event of its
# start-up timeline (JAX's own import is inside it where the caller has
# not imported JAX before)
from .obs.trace import STARTUP as _STARTUP, span as _span  # noqa: E402

__version__ = "0.1.0"

__all__ = ["reader", "dataset", "batch", "fluid", "v2", "infer",
           "layer", "image", "obs", "resilience", "analysis"]

with _span("startup/import", cat=_STARTUP).began(_IMPORT_BEGAN):
    from . import reader
    from . import dataset
    from .reader.decorator import batch
    from . import analysis
    from . import obs
    from . import resilience
    with _span("startup/import_fluid", cat=_STARTUP):
        from . import fluid
    with _span("startup/import_v2", cat=_STARTUP):
        from . import v2
        from .v2 import layer
        from .v2 import image
        from .v2.inference import infer
