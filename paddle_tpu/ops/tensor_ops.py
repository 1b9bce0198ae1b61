"""Tensor creation / manipulation op kernels.

TPU-native equivalents of the reference ops in paddle/operators/
(fill_constant_op.cc, assign_op.cc, cast_op.cc, concat_op.cc, split_op.cc,
reshape_op.cc, transpose_op.cc, expand_op.cc, sum_op.cc, scale_op.cc,
clip_op.cc, top_k_op.cc, gather_op.cc, scatter_op.cc, pad_op.cc,
crop_op.cc, increment_op.cc, multiplex_op.cc ...).  Each kernel is one pure
JAX function; XLA fuses them into the surrounding block.
"""

import numpy as np
import jax
import jax.numpy as jnp

from .registry import register_op
from ..core.types import np_dtype
from ..core.ragged import RaggedTensor, SelectedRows


def _x(ins, slot="X"):
    return ins[slot][0]


def _vals(v):
    return v.values if isinstance(v, RaggedTensor) else v


@register_op("fill_constant", stop_gradient_op=True)
def fill_constant(ctx, ins, attrs):
    shape = tuple(int(s) for s in attrs["shape"])
    dtype = np_dtype(attrs.get("dtype", "float32"))
    value = attrs.get("value", 0.0)
    return {"Out": [jnp.full(shape, value, dtype)]}


@register_op("fill_constant_batch_size_like", stop_gradient_op=True)
def fill_constant_batch_size_like(ctx, ins, attrs):
    ref = _vals(_x(ins, "Input"))
    shape = list(int(s) for s in attrs["shape"])
    in_idx = int(attrs.get("input_dim_idx", 0))
    out_idx = int(attrs.get("output_dim_idx", 0))
    shape[out_idx] = ref.shape[in_idx]
    dtype = np_dtype(attrs.get("dtype", "float32"))
    return {"Out": [jnp.full(tuple(shape), attrs.get("value", 0.0), dtype)]}


@register_op("fill_zeros_like", stop_gradient_op=True)
def fill_zeros_like(ctx, ins, attrs):
    x = _x(ins)
    if isinstance(x, RaggedTensor):
        return {"Out": [x.with_values(jnp.zeros_like(x.values))]}
    return {"Out": [jnp.zeros_like(x)]}


@register_op("assign")
def assign(ctx, ins, attrs):
    return {"Out": [_x(ins)]}


@register_op("assign_value", stop_gradient_op=True)
def assign_value(ctx, ins, attrs):
    shape = tuple(int(s) for s in attrs["shape"])
    dtype = np_dtype(attrs.get("dtype", "float32"))
    values = np.asarray(attrs["values"], dtype).reshape(shape)
    return {"Out": [jnp.asarray(values)]}


@register_op("fill", stop_gradient_op=True)
def fill(ctx, ins, attrs):
    """reference: fill_op.cc — materialize attr `data` into a tensor
    (the run-once / force_cpu knobs are placement details XLA owns)."""
    shape = tuple(int(s) for s in attrs["shape"])
    dtype = np_dtype(attrs.get("dtype", "float32"))
    values = np.asarray(attrs["data"], dtype).reshape(shape)
    return {"Out": [jnp.asarray(values)]}


@register_op("cast", nondiff_inputs=("Like",))
def cast(ctx, ins, attrs):
    x = _x(ins)
    if "Like" in ins:
        # the type another variable has as it runs: a Program states
        # float32 for a parameter that a scope may hold in bfloat16
        dtype = _vals(ins["Like"][0]).dtype
    else:
        dtype = np_dtype(attrs["out_dtype"] if "out_dtype" in attrs
                         else attrs["dtype"])
    if isinstance(x, RaggedTensor):
        return {"Out": [x.with_values(x.values.astype(dtype))]}
    return {"Out": [x.astype(dtype)]}


@register_op("concat")
def concat(ctx, ins, attrs):
    axis = int(attrs.get("axis", 0))
    xs = ins["X"]
    # feature-axis concat of ragged sequences stays ragged: the rows
    # line up step-for-step, so concat the values and keep row_splits
    # (axis-0 ragged concat is the separate sequence_concat op)
    ragged = next((v for v in xs if isinstance(v, RaggedTensor)), None)
    out = jnp.concatenate([_vals(v) for v in xs], axis)
    if ragged is not None and axis != 0:
        return {"Out": [ragged.with_values(out)]}
    return {"Out": [out]}


@register_op("split")
def split(ctx, ins, attrs):
    x = _x(ins)
    axis = int(attrs.get("axis", 0))
    sections = attrs.get("sections")
    num = attrs.get("num", 0)
    ragged = isinstance(x, RaggedTensor)
    vals = x.values if ragged else x
    if sections:
        idx = np.cumsum(sections[:-1]).tolist()
        parts = jnp.split(vals, idx, axis)
    else:
        parts = jnp.split(vals, int(num), axis)
    if ragged and axis != 0:
        parts = [x.with_values(p) for p in parts]
    return {"Out": list(parts)}


@register_op("reshape")
def reshape(ctx, ins, attrs):
    x = _x(ins)
    shape = [int(s) for s in attrs["shape"]]
    # reference reshape_op.cc: one -1 infers, 0 copies the input dim
    shape = [x.shape[i] if s == 0 else s for i, s in enumerate(shape)]
    return {"Out": [jnp.reshape(x, shape)]}


@register_op("transpose")
def transpose(ctx, ins, attrs):
    return {"Out": [jnp.transpose(_x(ins), attrs["axis"])]}


@register_op("expand")
def expand(ctx, ins, attrs):
    x = _x(ins)
    times = [int(t) for t in attrs["expand_times"]]
    return {"Out": [jnp.tile(x, times)]}


@register_op("sum")
def sum_op(ctx, ins, attrs):
    xs = ins["X"]
    if isinstance(xs[0], RaggedTensor):
        acc = xs[0].values
        for x in xs[1:]:
            acc = acc + _vals(x)
        return {"Out": [xs[0].with_values(acc)]}
    if isinstance(xs[0], SelectedRows) and all(
            isinstance(x, SelectedRows) for x in xs):
        rows = jnp.concatenate([x.rows for x in xs])
        values = jnp.concatenate([x.values for x in xs], 0)
        return {"Out": [SelectedRows(rows, values, xs[0].height)]}
    acc = None
    for x in xs:
        d = x.to_dense() if isinstance(x, SelectedRows) else _vals(x)
        acc = d if acc is None else acc + d
    return {"Out": [acc]}


@register_op("recompute_barrier", stop_gradient_op=True)
def recompute_barrier(ctx, ins, attrs):
    """Identity on X behind lax.optimization_barrier, so recomputed
    forward clones (fluid/recompute.py) can't be CSE'd into the
    originals; the Trigger operand (an incoming backward gradient) makes
    the clone data-depend on the backward front, so the scheduler can't
    hoist it next to the original forward."""
    vals = tuple(ins["X"]) + tuple(ins.get("Trigger", []))
    out = jax.lax.optimization_barrier(vals)
    return {"Out": list(out[:len(ins["X"])])}


@register_op("scale")
def scale(ctx, ins, attrs):
    x = _x(ins)
    s = attrs.get("scale", 1.0)
    if isinstance(x, RaggedTensor):
        return {"Out": [x.with_values(x.values * s)]}
    return {"Out": [x * s]}


@register_op("increment")
def increment(ctx, ins, attrs):
    x = _x(ins)
    return {"Out": [x + jnp.asarray(attrs.get("step", 1.0), x.dtype)]}


@register_op("sign")
def sign(ctx, ins, attrs):
    return {"Out": [jnp.sign(_x(ins))]}


@register_op("clip")
def clip(ctx, ins, attrs):
    return {"Out": [jnp.clip(_x(ins), attrs["min"], attrs["max"])]}


@register_op("clip_by_norm")
def clip_by_norm(ctx, ins, attrs):
    x = _x(ins)
    max_norm = attrs["max_norm"]
    norm = jnp.sqrt(jnp.sum(jnp.square(x)))
    scale = jnp.where(norm > max_norm, max_norm / jnp.maximum(norm, 1e-12),
                      1.0).astype(x.dtype)
    return {"Out": [x * scale]}


@register_op("top_k", nondiff_inputs=("X",))
def top_k(ctx, ins, attrs):
    x = _x(ins)
    k = int(attrs["k"])
    vals, idx = jax.lax.top_k(_vals(x), k)
    idx = idx.astype(jnp.int32)
    if isinstance(x, RaggedTensor):
        # per-step top-k of a sequence stays a sequence
        return {"Out": [x.with_values(vals)],
                "Indices": [x.with_values(idx)]}
    return {"Out": [vals], "Indices": [idx]}


@register_op("gather")
def gather(ctx, ins, attrs):
    x = _x(ins)
    index = jnp.reshape(ins["Index"][0], (-1,)).astype(jnp.int32)
    return {"Out": [jnp.take(x, index, axis=0)]}


@register_op("scatter")
def scatter(ctx, ins, attrs):
    # reference scatter_op.cc: Ref updated at Index rows with Updates
    ref = ins["Ref"][0]
    index = jnp.reshape(ins["Index"][0], (-1,)).astype(jnp.int32)
    updates = ins["Updates"][0]
    return {"Out": [ref.at[index].set(updates)]}


@register_op("pad")
def pad(ctx, ins, attrs):
    x = _x(ins)
    paddings = attrs["paddings"]  # flat [lo0, hi0, lo1, hi1, ...]
    cfg = [(int(paddings[2 * i]), int(paddings[2 * i + 1]))
           for i in range(x.ndim)]
    return {"Out": [jnp.pad(x, cfg, constant_values=attrs.get("pad_value",
                                                              0.0))]}


@register_op("crop")
def crop(ctx, ins, attrs):
    x = _x(ins)
    offsets = attrs["offsets"]
    shape = attrs["shape"]
    slices = tuple(slice(int(o), int(o) + int(s))
                   for o, s in zip(offsets, shape))
    return {"Out": [x[slices]]}


@register_op("slice")
def slice_op(ctx, ins, attrs):
    """reference: slice_op.cc — Input[starts[i]:ends[i]] along axes[i];
    a negative index counts from the end, an end past the extent is the
    extent; attr `own_layout`: the result in its declared layout,
    whatever reads it (fluid.layers.slice)."""
    x = _x(ins, "Input")
    index = [slice(None)] * x.ndim
    for axis, start, end in zip(attrs["axes"], attrs["starts"],
                                attrs["ends"]):
        index[int(axis)] = slice(int(start), int(end))
    out = x[tuple(index)]
    if attrs.get("own_layout"):
        # an array of its own, rows-major as declared: a transpose that
        # reads it turns these elements, not the array they were cut from
        from jax.experimental.layout import Layout, with_layout_constraint
        out = with_layout_constraint(
            out, Layout(major_to_minor=tuple(range(out.ndim))))
    return {"Out": [out]}


@register_op("cumsum")
def cumsum(ctx, ins, attrs):
    """reference: cum_op.cc — running sum along `axis`; `exclusive`
    leaves an element out of its own sum."""
    x = _x(ins)
    axis = int(attrs.get("axis", -1))
    out = jnp.cumsum(x, axis=axis)
    if attrs.get("exclusive"):
        # the sums before each element: shifted by one, not `out - x`,
        # which would leave rounding where the sum is empty
        out = jnp.concatenate(
            [jnp.zeros_like(jax.lax.slice_in_dim(x, 0, 1, axis=axis)),
             jax.lax.slice_in_dim(out, 0, x.shape[axis] - 1, axis=axis)],
            axis=axis)
    return {"Out": [out]}


@register_op("multiplex", nondiff_inputs=("Ids",))
def multiplex(ctx, ins, attrs):
    ids = jnp.reshape(ins["Ids"][0], (-1,)).astype(jnp.int32)
    stacked = jnp.stack([_vals(v) for v in ins["X"]], 0)  # [n, N, D]
    rows = jnp.arange(stacked.shape[1])
    return {"Out": [stacked[ids, rows]]}


@register_op("is_empty", stop_gradient_op=True)
def is_empty(ctx, ins, attrs):
    x = _vals(_x(ins))
    return {"Out": [jnp.asarray(x.size == 0)]}


@register_op("shape", stop_gradient_op=True)
def shape_op(ctx, ins, attrs):
    x = _vals(_x(ins, "Input"))
    return {"Out": [jnp.asarray(np.array(x.shape, np.int32))]}
