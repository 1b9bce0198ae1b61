"""Math op kernels: mul/matmul, elementwise family, reductions, norms.

TPU-native equivalents of reference ops (paddle/operators/mul_op.cc,
matmul_op.cc + operators/math/matmul.h, elementwise_op.h +
elementwise_op_function.h broadcasting engine, reduce_op.cc, minus_op.cc,
squared_l2_norm_op.cc, squared_l2_distance_op.cc, l1_norm_op.cc,
norm_op.cc, cos_sim_op.cc, logical_op.cc, compare_op.cc).

Matmuls are the MXU's food: `mul`/`matmul` lower straight to
jax.numpy.dot/matmul so XLA tiles them onto the systolic array; the
reference's cuBLAS wrapper layer has no equivalent here by design.
"""

import numpy as np

import jax
import jax.numpy as jnp

from .registry import register_op
from .amp_util import mxu_operands, acc_kwargs, amp_result, amp_harmonize
from ..core.ragged import RaggedTensor


def _x(ins, slot="X"):
    return ins[slot][0]


def _vals(v):
    return v.values if isinstance(v, RaggedTensor) else v


def _flatten2d(x, num_col_dims):
    """reference: framework/ddim flatten_to_2d used by mul_op."""
    lead = 1
    for d in x.shape[:num_col_dims]:
        lead *= d
    return jnp.reshape(x, (lead, -1))


def _float32_dot(x, w):
    """x w in float32 with neither operand rounded on the way, whatever
    the matrix unit's default for float32 operands is (on a TPU one
    bfloat16 pass, which rounds x to 8 bits of mantissa).  A bfloat16
    `w` is read as it lies and once: a float32 x is exactly the sum of
    three bfloat16 terms, which go through one product as three times
    the rows, every term's products exact and summed in float32, the
    smallest term's rows added first.  Any other `w`: the highest
    precision."""
    f32 = jnp.float32
    x = x.astype(f32)
    if w.dtype != jnp.bfloat16:
        return jnp.dot(x, w.astype(f32), precision=jax.lax.Precision.HIGHEST)
    terms, rest = [], x
    for _ in range(3):
        terms.append(rest.astype(jnp.bfloat16))
        rest = rest - terms[-1].astype(f32)
    rows = x.shape[0]
    out = jnp.dot(jnp.concatenate(terms[::-1]), w,
                  preferred_element_type=f32)
    return out[:rows] + out[rows:2 * rows] + out[2 * rows:]


@register_op("mul")
def mul(ctx, ins, attrs):
    x, y = _vals(_x(ins)), _vals(_x(ins, "Y"))
    xn = int(attrs.get("x_num_col_dims", 1))
    yn = int(attrs.get("y_num_col_dims", 1))
    x2 = _flatten2d(x, xn)
    y2 = _flatten2d(y, yn)
    dtype = jnp.result_type(x.dtype, y.dtype)
    if attrs.get("float32"):
        # a head asked for in float32 (`decoder_block.linear_float32`)
        out = _float32_dot(x2, y2)
    else:
        x2, y2 = mxu_operands(x2, y2)
        out = amp_result(jnp.dot(x2, y2, **acc_kwargs(x2, y2)), dtype)
    out_shape = x.shape[:xn] + y.shape[yn:]
    out = jnp.reshape(out, out_shape)
    xin = ins["X"][0]
    if isinstance(xin, RaggedTensor):
        return {"Out": [xin.with_values(out)]}
    return {"Out": [out]}


@register_op("matmul")
def matmul(ctx, ins, attrs):
    x, y = _vals(_x(ins)), _vals(_x(ins, "Y"))
    if attrs.get("transpose_X"):
        x = jnp.swapaxes(x, -1, -2) if x.ndim > 1 else x
    if attrs.get("transpose_Y"):
        y = jnp.swapaxes(y, -1, -2) if y.ndim > 1 else y
    dtype = jnp.result_type(x.dtype, y.dtype)
    xm, ym = mxu_operands(x, y)
    out = jnp.matmul(xm, ym, **acc_kwargs(xm, ym))
    return {"Out": [amp_result(out, dtype)]}


# -- elementwise family ------------------------------------------------------

def _bcast_y(x, y, axis):
    """reference: elementwise_op_function.h — Y broadcast into X starting at
    `axis` (default: trailing alignment)."""
    if x.shape == y.shape:
        return y
    if axis is None or axis == -1:
        return y
    axis = int(axis)
    pad_after = x.ndim - axis - y.ndim
    new_shape = (1,) * axis + y.shape + (1,) * pad_after
    return jnp.reshape(y, new_shape)


def _ew(name, fn):
    @register_op(name)
    def kernel(ctx, ins, attrs, fn=fn):
        xr, yr = ins["X"][0], ins["Y"][0]
        x, y = _vals(xr), _vals(yr)
        x, y = amp_harmonize(x, y)
        out = fn(x, _bcast_y(x, y, attrs.get("axis", -1)))
        if isinstance(xr, RaggedTensor):
            return {"Out": [xr.with_values(out)]}
        return {"Out": [out]}
    kernel.__name__ = name
    return kernel


_ew("elementwise_add", lambda x, y: x + y)
_ew("elementwise_sub", lambda x, y: x - y)
_ew("elementwise_mul", lambda x, y: x * y)
_ew("elementwise_div", lambda x, y: x / y)
_ew("elementwise_max", jnp.maximum)
_ew("elementwise_min", jnp.minimum)
_ew("elementwise_pow", jnp.power)


@register_op("minus")
def minus(ctx, ins, attrs):
    return {"Out": [_vals(_x(ins)) - _vals(_x(ins, "Y"))]}


# -- reductions --------------------------------------------------------------

def _reduce(name, fn, acc_f32=False):
    @register_op(name)
    def kernel(ctx, ins, attrs, fn=fn):
        xr = _x(ins)
        x = _vals(xr)
        if acc_f32 and x.dtype == jnp.bfloat16:
            # sum-style reductions accumulate in f32 (bf16's 8 mantissa
            # bits saturate after a few hundred ~1.0 addends); max/min
            # reductions are exact in any dtype and skip this
            x = x.astype(jnp.float32)
        dim = int(attrs.get("dim", 0))
        if dim < 0:
            dim += x.ndim
        # a reduction that crosses the ragged ROW axis must not fold
        # bucket-padding rows into the result (same contract as `mean`)
        if isinstance(xr, RaggedTensor) and (attrs.get("reduce_all",
                                                       False)
                                             or dim == 0):
            mask = xr.valid_mask().reshape(
                (-1,) + (1,) * (x.ndim - 1))
            if name == "reduce_sum":
                x = jnp.where(mask, x, jnp.zeros_like(x))
            elif name == "reduce_mean":
                # masked sum / valid count, broadcast over features
                total = jnp.sum(jnp.where(mask, x, jnp.zeros_like(x)),
                                axis=None
                                if attrs.get("reduce_all", False) else 0)
                denom = jnp.maximum(xr.nvalid, 1).astype(total.dtype)
                if attrs.get("reduce_all", False):
                    feat = max(1, int(np.prod(x.shape[1:])))
                    out = total / (denom * feat)
                    out = jnp.reshape(out, (1,) * x.ndim
                                      if attrs.get("keep_dim", False)
                                      else (1,))
                    return {"Out": [out]}
                out = total / denom
                if attrs.get("keep_dim", False):
                    out = jnp.expand_dims(out, 0)
                return {"Out": [out]}
            else:
                # dtype-aware identity element for max/min over pads
                info = (jnp.iinfo(x.dtype)
                        if jnp.issubdtype(x.dtype, jnp.integer)
                        else jnp.finfo(x.dtype))
                neutral = jnp.asarray(
                    info.min if name == "reduce_max" else info.max,
                    x.dtype)
                x = jnp.where(mask, x, neutral)
        if attrs.get("reduce_all", False):
            out = fn(x, axis=None)
            out = jnp.reshape(out, (1,) * x.ndim
                              if attrs.get("keep_dim", False) else (1,))
            return {"Out": [out]}
        out = fn(x, axis=dim)
        if attrs.get("keep_dim", False):
            out = jnp.expand_dims(out, dim)
        # reducing a feature axis of a ragged sequence keeps one row per
        # step: still a sequence (keep_dim preserves the row axis)
        if isinstance(xr, RaggedTensor) and dim != 0 \
                and attrs.get("keep_dim", False):
            return {"Out": [xr.with_values(out)]}
        return {"Out": [out]}
    kernel.__name__ = name
    return kernel


_reduce("reduce_sum", jnp.sum, acc_f32=True)
_reduce("reduce_mean", jnp.mean, acc_f32=True)
_reduce("reduce_max", jnp.max)
_reduce("reduce_min", jnp.min)


@register_op("mean")
def mean(ctx, ins, attrs):
    # scalar outputs are shape-(1,) tensors, matching the reference's
    # convention for scalars (mean_op.cc InferShape -> {1}); a bf16
    # input (FLAGS_amp_bf16_act) accumulates in f32 — this is almost
    # always the final loss reduction
    xr = _x(ins)
    x = _vals(xr)
    if x.dtype == jnp.bfloat16:
        x = x.astype(jnp.float32)
    from ..core.ragged import RaggedTensor

    if isinstance(xr, RaggedTensor):
        # a ragged loss means per-token rows padded to the bucket: the
        # mean must cover VALID rows only, or every padded row's
        # garbage (-log eps after a masked softmax) drowns the signal
        rows = x.reshape(x.shape[0], -1)
        mask = xr.valid_mask().astype(rows.dtype)
        total = jnp.sum(rows * mask[:, None])
        denom = xr.nvalid.astype(rows.dtype) * rows.shape[1]
        return {"Out": [jnp.reshape(total / jnp.maximum(denom, 1), (1,))]}
    return {"Out": [jnp.reshape(jnp.mean(x), (1,))]}


@register_op("squared_l2_norm")
def squared_l2_norm(ctx, ins, attrs):
    return {"Out": [jnp.sum(jnp.square(_vals(_x(ins))))]}


@register_op("isfinite", stop_gradient_op=True, nondiff_inputs=("X",))
def isfinite(ctx, ins, attrs):
    # reference: the CheckTensorNANOrInf scan (executor.cc:66-77) as an
    # op: one bool — does X hold only finite values?  Jit-safe, so the
    # numerics health monitor can run it inside a compiled segment.
    x = _vals(_x(ins))
    return {"Out": [jnp.reshape(jnp.all(jnp.isfinite(x)), (1,))]}


@register_op("count_nonfinite", stop_gradient_op=True,
             nondiff_inputs=("X",))
def count_nonfinite(ctx, ins, attrs):
    # int32 count of NaN/Inf elements in X — the on-device reduction
    # behind `numerics_nonfinite_total` (obs/health.py); XLA fuses it
    # into the surrounding segment, no extra HBM pass
    x = _vals(_x(ins))
    bad = jnp.logical_not(jnp.isfinite(x))
    return {"Out": [jnp.reshape(jnp.sum(bad, dtype=jnp.int32), (1,))]}


@register_op("l1_norm")
def l1_norm(ctx, ins, attrs):
    return {"Out": [jnp.sum(jnp.abs(_vals(_x(ins))))]}


@register_op("squared_l2_distance")
def squared_l2_distance(ctx, ins, attrs):
    x, y = _vals(_x(ins)), _vals(_x(ins, "Y"))
    sub = x - y
    out = jnp.sum(jnp.square(sub), axis=tuple(range(1, sub.ndim)),
                  keepdims=True)
    return {"sub_result": [sub], "Out": [jnp.reshape(out, (x.shape[0], 1))]}


@register_op("cos_sim")
def cos_sim(ctx, ins, attrs):
    x, y = _vals(_x(ins)), _vals(_x(ins, "Y"))
    xnorm = jnp.sqrt(jnp.sum(jnp.square(x), -1, keepdims=True))
    ynorm = jnp.sqrt(jnp.sum(jnp.square(y), -1, keepdims=True))
    prod = jnp.sum(x * y, -1, keepdims=True)
    out = prod / (xnorm * ynorm + 1e-12)
    return {"Out": [out], "XNorm": [xnorm], "YNorm": [ynorm]}


# -- comparison / logical ----------------------------------------------------

def _cmp(name, fn):
    @register_op(name, stop_gradient_op=True, nondiff_inputs=("X", "Y"))
    def kernel(ctx, ins, attrs, fn=fn):
        return {"Out": [fn(_vals(_x(ins)), _vals(_x(ins, "Y")))]}
    kernel.__name__ = name
    return kernel


_cmp("less_than", lambda x, y: x < y)
_cmp("less_equal", lambda x, y: x <= y)
_cmp("greater_than", lambda x, y: x > y)
_cmp("greater_equal", lambda x, y: x >= y)
_cmp("equal", lambda x, y: x == y)
_cmp("not_equal", lambda x, y: x != y)
_cmp("logical_and", jnp.logical_and)
_cmp("logical_or", jnp.logical_or)
_cmp("logical_xor", jnp.logical_xor)


@register_op("logical_not", stop_gradient_op=True, nondiff_inputs=("X",))
def logical_not(ctx, ins, attrs):
    return {"Out": [jnp.logical_not(_vals(_x(ins)))]}
