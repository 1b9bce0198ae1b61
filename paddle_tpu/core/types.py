"""Core type system: variable kinds and dtype mapping.

TPU-native equivalent of the reference IR's type enums
(reference: paddle/framework/framework.proto:91-117 VarDesc.VarType,
framework.proto:19-28 DataType).  Dtypes canonicalise onto JAX dtypes;
int64/float64 are kept in descs for API parity but execute as the JAX
canonical types (TPUs are int32/bf16/f32-first).
"""

import numpy as np


class VarType:
    """Variable kinds (reference: framework.proto VarDesc.VarType)."""

    DENSE_TENSOR = "dense_tensor"          # reference: LOD_TENSOR
    SELECTED_ROWS = "selected_rows"
    FEED_MINIBATCH = "feed_minibatch"
    FETCH_LIST = "fetch_list"
    STEP_SCOPES = "step_scopes"
    LOD_RANK_TABLE = "lod_rank_table"
    TENSOR_ARRAY = "tensor_array"          # reference: LOD_TENSOR_ARRAY
    PLACE_LIST = "place_list"
    READER = "reader"
    RAW = "raw"

    # alias kept for user-facing parity with the reference API
    LOD_TENSOR = DENSE_TENSOR
    LOD_TENSOR_ARRAY = TENSOR_ARRAY


_DTYPE_ALIASES = {
    "float32": "float32",
    "float64": "float64",
    "float16": "float16",
    "bfloat16": "bfloat16",
    "int8": "int8",
    "int16": "int16",
    "int32": "int32",
    "int64": "int64",
    "uint8": "uint8",
    "uint32": "uint32",
    "bool": "bool",
    "float": "float32",
    "double": "float64",
    "int": "int32",
    "long": "int64",
}

# What actually runs on device.  JAX without x64 canonicalises 64-bit types;
# we do it explicitly so feed/compile keys are stable.
_EXEC_DTYPE = {
    "float64": "float32",
    "int64": "int32",
    "uint64": "uint32",
}


def canonical_dtype(dtype) -> str:
    """Normalise any user dtype spec to a canonical string name."""
    if isinstance(dtype, str):
        name = dtype
    else:
        name = np.dtype(dtype).name
    if name not in _DTYPE_ALIASES:
        raise ValueError("unsupported dtype: %r" % (dtype,))
    return _DTYPE_ALIASES[name]


def exec_dtype(dtype) -> str:
    """The dtype a declared dtype executes as on the accelerator."""
    name = canonical_dtype(dtype)
    return _EXEC_DTYPE.get(name, name)


def np_dtype(dtype):
    import jax.numpy as jnp

    return jnp.dtype(exec_dtype(dtype))


def is_float_dtype(dtype) -> bool:
    return canonical_dtype(dtype) in (
        "float16", "bfloat16", "float32", "float64")


GRAD_SUFFIX = "@GRAD"


def grad_var_name(name: str) -> str:
    """reference: paddle/framework/grad_op_desc_maker.h GradVarName."""
    return name + GRAD_SUFFIX
