"""Operator catalogue: every op kernel registers itself on import.

TPU-native re-design of paddle/operators/ (~160 op families).  Kernels are
pure JAX functions fused by XLA at block granularity; see registry.py for
the contract.
"""

from . import registry
from .registry import (register_op, register_grad_kernel, get_op_info,
                       has_op, registered_ops)

from . import tensor_ops    # noqa: F401
from . import math          # noqa: F401
from . import activation    # noqa: F401
from . import loss          # noqa: F401
from . import random        # noqa: F401
from . import optimizer_ops  # noqa: F401
from . import metrics       # noqa: F401
from . import io_ops        # noqa: F401
from . import conv          # noqa: F401
from . import norm          # noqa: F401
from . import sparse        # noqa: F401
from . import nn            # noqa: F401
from . import attention     # noqa: F401
from . import moe           # noqa: F401
from . import ssm           # noqa: F401
from . import linear_attention  # noqa: F401
from . import hyper_connection  # noqa: F401
from . import sequence      # noqa: F401
from . import control_flow  # noqa: F401
from . import crf           # noqa: F401
from . import ctc           # noqa: F401
from . import beam          # noqa: F401
from . import detection     # noqa: F401
from . import dist          # noqa: F401
from . import v2_extra      # noqa: F401
