"""Tensor layers (reference: python/paddle/v2/fluid/layers/tensor.py)."""

from ..layer_helper import LayerHelper
from ..framework import Variable, default_main_program, default_startup_program
from ..initializer import Constant

__all__ = [
    "create_tensor", "create_parameter", "create_global_var", "cast",
    "concat", "sums", "assign", "fill_constant",
    "fill_constant_batch_size_like", "ones", "zeros", "reshape",
    "split_lod_tensor", "merge_lod_tensor", "increment",
]


def create_tensor(dtype, name=None, persistable=False, **kwargs):
    helper = LayerHelper("create_tensor", name=name, **kwargs)
    return helper.create_variable(name=helper.name, dtype=dtype,
                                  persistable=persistable)


def create_parameter(shape, dtype, attr=None, is_bias=False,
                     default_initializer=None, **kwargs):
    from ..param_attr import ParamAttr

    helper = LayerHelper("create_parameter", **kwargs)
    attr = ParamAttr.to_attr(attr)
    return helper.create_parameter(attr, shape, dtype, is_bias,
                                   default_initializer)


def create_global_var(shape, value, dtype, persistable=False, name=None,
                      **kwargs):
    helper = LayerHelper("global_var", name=name, **kwargs)
    var = helper.create_global_variable(
        dtype=dtype, shape=shape, persistable=persistable, name=name)
    helper.set_variable_initializer(var, Constant(value))
    return var


def cast(x, dtype, **kwargs):
    """`x` in `dtype`: a type's name, or a Variable, for the type that
    variable has as the Program runs (a Program states float32 for a
    parameter, and for all that is computed from it, where a scope may
    hold the parameter in bfloat16)."""
    helper = LayerHelper("cast", **kwargs)
    inputs = {"X": [x]}
    if isinstance(dtype, Variable):
        inputs["Like"], dtype = [dtype], dtype.dtype
    out = helper.create_tmp_variable(dtype, lod_level=x.lod_level)
    helper.append_op(type="cast", inputs=inputs, outputs={"Out": [out]},
                     attrs={"in_dtype": x.dtype, "out_dtype": dtype})
    return out


def concat(input, axis=0, **kwargs):
    helper = LayerHelper("concat", **kwargs)
    # feature-axis concat of ragged sequences stays ragged; axis-0
    # concat flattens to dense (sequence_concat is the ragged axis-0 op)
    lod = 0 if axis == 0 else max(getattr(x, "lod_level", 0)
                                  for x in input)
    out = helper.create_tmp_variable(helper.input_dtype, lod_level=lod)
    helper.append_op(type="concat", inputs={"X": input},
                     outputs={"Out": [out]}, attrs={"axis": axis})
    return out


def sums(input, out=None, **kwargs):
    helper = LayerHelper("sum", **kwargs)
    if out is None:
        out = helper.create_tmp_variable(helper.input_dtype)
    helper.append_op(type="sum", inputs={"X": input},
                     outputs={"Out": [out]})
    return out


def assign(input, output, **kwargs):
    helper = LayerHelper("assign", **kwargs)
    if isinstance(input, Variable):
        helper.append_op(type="assign", inputs={"X": [input]},
                         outputs={"Out": [output]})
    else:
        import numpy as np

        arr = np.asarray(input)
        helper.append_op(
            type="assign_value", outputs={"Out": [output]},
            attrs={"shape": list(arr.shape), "dtype": str(arr.dtype),
                   "values": arr.reshape(-1).tolist()})
    return output


def fill_constant(shape, dtype, value, out=None, **kwargs):
    helper = LayerHelper("fill_constant", **kwargs)
    if out is None:
        out = helper.create_tmp_variable(dtype, stop_gradient=True)
    helper.append_op(
        type="fill_constant", outputs={"Out": [out]},
        attrs={"shape": [int(s) for s in shape], "dtype": dtype,
               "value": float(value)})
    out.stop_gradient = True
    return out


def fill_constant_batch_size_like(input, shape, dtype, value,
                                  input_dim_idx=0, output_dim_idx=0,
                                  **kwargs):
    helper = LayerHelper("fill_constant_batch_size_like", **kwargs)
    out = helper.create_tmp_variable(dtype, stop_gradient=True)
    helper.append_op(
        type="fill_constant_batch_size_like",
        inputs={"Input": [input]}, outputs={"Out": [out]},
        attrs={"shape": [int(s) for s in shape], "dtype": dtype,
               "value": float(value), "input_dim_idx": input_dim_idx,
               "output_dim_idx": output_dim_idx})
    return out


def ones(shape, dtype, **kwargs):
    return fill_constant(shape=shape, dtype=dtype, value=1.0, **kwargs)


def zeros(shape, dtype, **kwargs):
    return fill_constant(shape=shape, dtype=dtype, value=0.0, **kwargs)


def reshape(x, shape, act=None, **kwargs):
    helper = LayerHelper("reshape", **kwargs)
    out = helper.create_tmp_variable(x.dtype)
    helper.append_op(type="reshape", inputs={"X": [x]},
                     outputs={"Out": [out]},
                     attrs={"shape": [int(s) for s in shape]})
    if act:
        return _act(helper, out, act)
    return out


def _act(helper, var, act):
    tmp = helper.create_tmp_variable(var.dtype)
    helper.append_op(type=act, inputs={"X": [var]}, outputs={"Out": [tmp]})
    return tmp


def increment(x, value=1.0, in_place=True, **kwargs):
    helper = LayerHelper("increment", **kwargs)
    if in_place:
        out = x
    else:
        out = helper.create_tmp_variable(x.dtype)
    helper.append_op(type="increment", inputs={"X": [x]},
                     outputs={"Out": [out]}, attrs={"step": float(value)})
    return out


def split_lod_tensor(input, mask, level=0, **kwargs):
    helper = LayerHelper("split_lod_tensor", **kwargs)
    out_true = helper.create_tmp_variable(input.dtype,
                                          lod_level=input.lod_level)
    out_false = helper.create_tmp_variable(input.dtype,
                                           lod_level=input.lod_level)
    helper.append_op(
        type="split_lod_tensor",
        inputs={"X": [input], "Mask": [mask]},
        outputs={"OutTrue": [out_true], "OutFalse": [out_false]},
        attrs={"level": level})
    return out_true, out_false


def merge_lod_tensor(in_true, in_false, x, mask, level=0, **kwargs):
    helper = LayerHelper("merge_lod_tensor", **kwargs)
    out = helper.create_tmp_variable(x.dtype, lod_level=x.lod_level)
    helper.append_op(
        type="merge_lod_tensor",
        inputs={"X": [x], "Mask": [mask], "InTrue": [in_true],
                "InFalse": [in_false]},
        outputs={"Out": [out]}, attrs={"level": level})
    return out
