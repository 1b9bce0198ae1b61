"""Operator registry: kernels, shape inference, grad makers.

TPU-native re-design of the reference op registry
(reference: paddle/framework/op_registry.h:148 REGISTER_OP,
op_registry.h:192-196 kernel registration, op_info.h:34 OpInfo).

Key departures from the reference, by design:
  * a "kernel" here is one pure JAX function per op (ins dict -> outs dict);
    XLA compiles and fuses whole blocks, so there is no per-device kernel
    dispatch table — placement is a property of the executor, not the op.
  * gradients: ops still get symbolic `<type>_grad` ops appended to the
    program (matching reference backward.cc semantics), but the *kernel* of
    a grad op is derived automatically with `jax.vjp` of the forward kernel
    unless an explicit grad kernel is registered (needed only where the
    reference has special semantics: dropout masks, sparse lookup_table
    grads, control flow).
  * shape inference defaults to `jax.eval_shape` over the kernel with a
    two-sample substitution for dynamic (-1) dims, replacing the
    hand-written per-op InferShape functions (reference:
    framework/shape_inference.h) for most ops.
"""

import numpy as np

import jax
import jax.numpy as jnp

from ..core.types import is_float_dtype, np_dtype, GRAD_SUFFIX, VarType


class OpInfo:
    __slots__ = ("type", "kernel", "infer_shape", "grad_maker", "grad_kernel",
                 "jittable", "uses_rng", "nondiff_inputs", "stop_gradient_op",
                 "in_place_outputs", "sparse_grad_slots")

    def __init__(self, type, kernel=None, infer_shape=None, grad_maker=None,
                 grad_kernel=None, jittable=True, uses_rng=False,
                 nondiff_inputs=(), stop_gradient_op=False,
                 in_place_outputs=(), sparse_grad_slots=None):
        self.type = type
        self.kernel = kernel
        self.infer_shape = infer_shape
        self.grad_maker = grad_maker          # None => generic maker
        self.grad_kernel = grad_kernel        # None => generic vjp kernel
        self.jittable = jittable
        self.uses_rng = uses_rng
        self.nondiff_inputs = tuple(nondiff_inputs)  # slots never differentiated
        self.stop_gradient_op = stop_gradient_op     # no grads flow at all
        # slots whose output aliases an input (optimizer ops: ParamOut=Param)
        self.in_place_outputs = tuple(in_place_outputs)
        # fn(attrs) -> forward-input slots whose grad is a SelectedRows;
        # the backward builder types those grad VarDescs accordingly
        # (reference: lookup_table_op.cc LookupTableOpGradVarTypeInference)
        self.sparse_grad_slots = sparse_grad_slots


_OP_REGISTRY = {}


def register_op(type, **kwargs):
    """Decorator registering `fn` as the kernel for op `type`.

    Kernel signature: fn(ctx, ins, attrs) -> outs
      ins/outs: dict slot -> list of values (jax arrays / RaggedTensor /
      SelectedRows / host objects); attrs: dict.
      ctx: ExecContext (rng, sub-block lowering); pure ops ignore it.
    """

    def deco(fn):
        info = OpInfo(type, kernel=fn, **kwargs)
        _OP_REGISTRY[type] = info
        return fn

    return deco


def register_grad_kernel(fwd_type):
    """Register an explicit kernel for `<fwd_type>_grad`."""

    def deco(fn):
        _OP_REGISTRY[fwd_type].grad_kernel = fn
        return fn

    return deco


def get_op_info(type):
    info = _OP_REGISTRY.get(type)
    if info is None:
        raise KeyError("operator %r is not registered" % type)
    return info


def has_op(type):
    return type in _OP_REGISTRY


def registered_ops():
    return sorted(_OP_REGISTRY.keys())


def is_grad_op_type(type):
    return type.endswith("_grad")


def forward_type_of_grad(type):
    assert is_grad_op_type(type)
    return type[: -len("_grad")]


# ---------------------------------------------------------------------------
# Generic shape inference
# ---------------------------------------------------------------------------

# all dynamic (-1) dims substitute the SAME value within one inference
# run (they are almost always the batch/token dim and must broadcast
# together); two runs with different values tell static dims from
# dynamic ones.  The substitutes are highly composite (840 = lcm 1..8,
# 2520 = lcm 1..9) rather than prime so kernels that FOLD the dynamic
# dim — reshape [-1, heads, ...] in multi-head attention, microbatch
# splits — see a divisible size.  Trade-off vs the old coprime primes:
# an output dim computed as a REMAINDER by a common divisor of both
# substitutes collapses to the same value in both runs and would be
# misread as static; no kernel does that today, and fold/split
# divisibility matters more than collision resistance here.
_SUB_A = 840
_SUB_B = 2520


class _NullCtx:
    """Placeholder ExecContext for shape inference: deterministic rng, no
    sub-block access (ops with sub-blocks must provide explicit
    infer_shape)."""

    def next_rng(self):
        return jax.random.PRNGKey(0)

    def lower_block(self, *a, **k):
        raise RuntimeError(
            "ops with sub-blocks need an explicit infer_shape")


def _abstract_inputs(ins_meta, sub_val):
    """ins_meta: slot -> list of (shape, dtype, lod_level[, var_type]).
    Returns abstract values with every -1 dim substituted by
    `sub_val`."""
    from ..core.ragged import RaggedTensor, SelectedRows

    def sub(shape):
        return tuple(sub_val if (d is None or d < 0) else int(d)
                     for d in shape)

    abstract = {}
    for slot, metas in ins_meta.items():
        vals = []
        for meta in metas:
            (shape, dtype, lod_level), rest = meta[:3], meta[3:]
            vtype = rest[0] if rest else VarType.DENSE_TENSOR
            if vtype == VarType.SELECTED_ROWS:
                # rows count is dynamic; height = shape[0] is static
                height = int(shape[0]) if shape and shape[0] and \
                    shape[0] > 0 else sub_val
                sr = SelectedRows.tree_unflatten(height, (
                    jax.ShapeDtypeStruct((sub_val,), jnp.int32),
                    jax.ShapeDtypeStruct((sub_val,) + sub(shape)[1:],
                                         np_dtype(dtype))))
                vals.append(sr)
                continue
            sds = jax.ShapeDtypeStruct(sub(shape), np_dtype(dtype))
            if lod_level and lod_level > 0:
                splits = [jax.ShapeDtypeStruct((sub_val + 1,), jnp.int32)
                          for _ in range(lod_level)]
                rt = RaggedTensor.tree_unflatten(
                    lod_level,
                    (sds, splits, jax.ShapeDtypeStruct((), jnp.int32)))
                vals.append(rt)
            else:
                vals.append(sds)
        abstract[slot] = vals
    return abstract


def generic_infer_shape(op_type, ins_meta, attrs):
    """Infer output (shape, dtype, lod_level) per slot.  Dims that differ
    between the two substitutions are reported as -1 (dynamic)."""
    info = get_op_info(op_type)
    kernel = info.kernel

    def run(sub_val):
        abstract = _abstract_inputs(ins_meta, sub_val)
        return jax.eval_shape(lambda i: kernel(_NullCtx(), i, attrs), abstract)

    has_dynamic = any(
        (d is None or d < 0)
        for metas in ins_meta.values()
        for meta in metas
        for d in meta[0]) or any(
        meta[2] > 0 or (len(meta) > 3 and
                        meta[3] == VarType.SELECTED_ROWS)
        for metas in ins_meta.values() for meta in metas)

    out_a = run(_SUB_A)
    out_b = run(_SUB_B) if has_dynamic else out_a

    from ..core.ragged import RaggedTensor, SelectedRows

    result = {}
    for slot in out_a:
        metas = []
        for va, vb in zip(out_a[slot], out_b[slot]):
            vtype = VarType.DENSE_TENSOR
            if isinstance(va, RaggedTensor):
                shape_a, shape_b = va.values.shape, vb.values.shape
                dtype = va.values.dtype
                lod = va.lod_level
            elif isinstance(va, SelectedRows):
                shape_a = (va.height,) + tuple(va.values.shape[1:])
                shape_b = (vb.height,) + tuple(vb.values.shape[1:])
                dtype = va.values.dtype
                lod = 0
                vtype = VarType.SELECTED_ROWS
            else:
                shape_a, shape_b = va.shape, vb.shape
                dtype = va.dtype
                lod = 0
            shape = tuple(
                int(da) if da == db else -1
                for da, db in zip(shape_a, shape_b))
            metas.append((shape, jnp.dtype(dtype).name, lod, vtype))
        result[slot] = metas
    return result


def same_meta_infer_shape(in_slot, out_slot):
    """An explicit shape rule for an op whose output has its input's
    shape, type and LoD: the build then copies the meta instead of
    tracing the kernel (registry.generic_infer_shape)."""
    def infer(block, op_desc):
        src = block.var_recursive(op_desc.input(in_slot)[0]).desc
        for name in op_desc.output(out_slot):
            dst = block.var_recursive(name).desc
            dst.shape, dst.dtype = tuple(src.shape), src.dtype
            dst.lod_level = src.lod_level
    return infer


# ---------------------------------------------------------------------------
# Generic vjp-based grad kernel
# ---------------------------------------------------------------------------

def _cotangent_for(primal, grad):
    """Build a vjp cotangent matching `primal`'s pytree structure: float
    leaves take the provided grad leaf (or zeros), non-float leaves take
    float0 zeros (jax's tangent type for integers)."""
    p_leaves, tdef = jax.tree_util.tree_flatten(primal)
    if grad is None:
        g_leaves = [None] * len(p_leaves)
    else:
        g_leaves = jax.tree_util.tree_leaves(grad)
        if len(g_leaves) != len(p_leaves):
            raise ValueError("grad/primal structure mismatch")

    fixed = []
    for p, g in zip(p_leaves, g_leaves):
        p = jnp.asarray(p)
        if jnp.issubdtype(p.dtype, jnp.floating):
            if g is None:
                fixed.append(jnp.zeros_like(p))
            else:
                g = jnp.asarray(g, p.dtype)
                if g.shape != p.shape:
                    g = jnp.reshape(g, p.shape)
                fixed.append(g)
        else:
            fixed.append(np.zeros(p.shape, jax.dtypes.float0))
    return jax.tree_util.tree_unflatten(tdef, fixed)


def run_generic_grad(ctx, fwd_type, ins, attrs):
    """Execute `<fwd_type>_grad` with inputs laid out by the generic grad
    maker (see backward.py; reference: grad_op_desc_maker.h
    DefaultGradOpDescMaker which forwards Input/Output/OutputGrad):
      ins[slot]       : forward inputs (original slots)
      ins["O@SLOT"]   : forward outputs (ignored here: jax.vjp runs the
                        forward kernel again, and XLA CSEs that against
                        the forward pass where it is XLA's own ops.  Not
                        where it is a custom call: an op whose forward
                        is a Pallas kernel registers an explicit grad
                        kernel that reads them, as flash_attention does)
      ins["OG@SLOT"]  : grads of forward outputs (may be absent)
    Returns {"SLOT@GRAD": [...]} for differentiable forward input slots.
    """
    info = get_op_info(fwd_type)
    if info.uses_rng:
        raise RuntimeError(
            "op %r consumes RNG; register an explicit grad kernel" % fwd_type)

    fwd_in = {}
    out_grads = {}
    for slot, vals in ins.items():
        if slot.startswith("OG@"):
            out_grads[slot[len("OG@"):]] = vals
        elif slot.startswith("O@"):
            continue
        else:
            fwd_in[slot] = vals

    diff_part = {}
    static_part = {}
    for slot, vals in fwd_in.items():
        if slot in info.nondiff_inputs:
            static_part[slot] = vals
        else:
            # differentiate float leaves; int leaves get float0 grads,
            # dropped below
            diff_part[slot] = vals

    def f(dpart):
        merged = dict(static_part)
        merged.update(dpart)
        return info.kernel(ctx, merged, attrs)

    primals_out, vjp_fn = jax.vjp(f, diff_part)

    cots = {}
    for slot, vals in primals_out.items():
        gs = out_grads.get(slot)
        cots[slot] = [
            _cotangent_for(
                p, gs[i] if gs is not None and i < len(gs) else None)
            for i, p in enumerate(vals)]

    (grads,) = vjp_fn(cots)

    from ..core.ragged import RaggedTensor

    result = {}
    for slot, vals in grads.items():
        outs = []
        for g, p in zip(vals, fwd_in[slot]):
            if isinstance(p, RaggedTensor) and g is not None:
                # rebuild a well-formed ragged grad sharing the primal's
                # splits (vjp yields float0 placeholders for the int splits)
                g_vals = g.values if isinstance(g, RaggedTensor) else g
                g = p.with_values(jnp.asarray(g_vals, p.values.dtype))
            elif g is not None and hasattr(g, "dtype") and \
                    g.dtype == jax.dtypes.float0:
                g = None
            outs.append(g)
        result[slot + GRAD_SUFFIX] = outs
    return result
