"""Normalization op kernels: batch_norm, layer_norm, rms_norm, norm (l2).

TPU-native equivalents of reference ops (paddle/operators/
batch_norm_op.cc + cudnn variant, norm_op.cc; layer_norm is provided for
completeness though the snapshot predates it).  batch_norm has an explicit
grad kernel because its forward mutates running stats (in-place outputs)
which must not be differentiated through.
"""

import jax
import jax.numpy as jnp

from .registry import (register_op, register_grad_kernel,
                       same_meta_infer_shape)
from ..utils import flags


def _slot0(ins, slot):
    """First entry of an optional grad-op slot, or None.

    backward.py feeds forward outputs prefixed ``O@<slot>`` and output
    grads as ``OG@<slot>`` with absent grads mapped to None by the
    executor, so both "slot missing" and "slot empty" mean None here.
    """
    vs = ins.get(slot)
    return vs[0] if vs else None


def _stat_cotangent(ins, saved_slot, out_slot, momentum):
    """Total f32 cotangent reaching a batch statistic that is exposed
    both directly (Saved*) and blended into the running stat (*Out) at
    weight (1 - momentum); None when neither path carries a gradient."""
    g = _slot0(ins, saved_slot)
    total = None if g is None else g.astype(jnp.float32)
    g = _slot0(ins, out_slot)
    if g is not None:
        g = (1.0 - momentum) * g.astype(jnp.float32)
        total = g if total is None else total + g
    return total


def _bn_axes(x, layout):
    if layout == "NCHW":
        return (tuple(i for i in range(x.ndim) if i != 1),
                (1, -1) + (1,) * (x.ndim - 2))
    return tuple(range(x.ndim - 1)), (1,) * (x.ndim - 1) + (-1,)


def _bn_stats(x, axes):
    """Batch mean/var, always accumulated in f32 (XLA fuses the convert
    into the reduction, so a bf16 input is still read once at 2 B/elem).

    Shifted one-pass form: with a per-channel reference value s,
    var = E[(x-s)^2] - E[x-s]^2 and mean = E[x-s] + s.  Both reductions
    still share a single sweep over the activation (XLA fuses same-input
    reduces) — unlike jnp.var's two-pass (x - mean)^2 which reads the
    big tensor twice — but the shift removes the catastrophic
    cancellation of the naive E[x^2] - E[x]^2 when |mean| >> std (e.g.
    a first BN over raw 0-255 inputs).  s is the channel's first
    element: free to read, and any value near the data keeps the
    cancellation benign; max(., 0) guards the round-off edge."""
    xs = x if x.dtype == jnp.float32 else x.astype(jnp.float32)
    if not flags.get_flag("bn_shifted_stats"):
        m = jnp.mean(xs, axis=axes)
        msq = jnp.mean(jnp.square(xs), axis=axes)
        return m, jnp.maximum(msq - jnp.square(m), 0.0)
    first = tuple(slice(0, 1) if i in axes else slice(None)
                  for i in range(x.ndim))
    shift = jax.lax.stop_gradient(xs[first])
    d = xs - shift
    dm = jnp.mean(d, axis=axes)
    dsq = jnp.mean(jnp.square(d), axis=axes)
    var = jnp.maximum(dsq - jnp.square(dm), 0.0)
    return dm + jnp.reshape(shift, dm.shape), var


def _bn_normalize(x, scale, bias, m, v, eps, bshape):
    inv_std = jax.lax.rsqrt(v + eps)
    if x.dtype == jnp.bfloat16:
        # fold the f32 statistics into one per-channel affine and apply
        # it in bf16: the big tensor is read/written at 2 B/elem and the
        # chain fuses with the adjacent conv/relu/residual ops
        a = scale * inv_std
        b = bias - m * a
        return x * a.reshape(bshape).astype(x.dtype) + \
            b.reshape(bshape).astype(x.dtype)
    return (x - m.reshape(bshape)) * inv_std.reshape(bshape) * \
        scale.reshape(bshape) + bias.reshape(bshape)


@register_op("batch_norm", nondiff_inputs=("Mean", "Variance"))
def batch_norm(ctx, ins, attrs):
    """reference: batch_norm_op.cc — training mode uses batch statistics
    and updates running stats with `momentum`; test mode uses running
    stats."""
    x = ins["X"][0]
    scale = ins["Scale"][0]
    bias = ins["Bias"][0]
    mean = ins["Mean"][0]
    variance = ins["Variance"][0]
    eps = attrs.get("epsilon", 1e-5)
    momentum = attrs.get("momentum", 0.9)
    is_test = attrs.get("is_test", False)
    layout = attrs.get("data_layout", "NCHW")

    axes, bshape = _bn_axes(x, layout)

    if is_test:
        use_mean, use_var = mean, variance
        mean_out, var_out = mean, variance
        saved_mean = mean
        saved_var = variance
    else:
        use_mean, use_var = _bn_stats(x, axes)
        mean_out = momentum * mean + (1 - momentum) * use_mean
        var_out = momentum * variance + (1 - momentum) * use_var
        saved_mean = use_mean
        saved_var = use_var

    y = _bn_normalize(x, scale, bias, use_mean, use_var, eps, bshape)
    # SavedVariance deliberately diverges from the reference:
    # batch_norm_op.cc inverts it in-place to inverse-std in the
    # forward ("SavedVariance have been reverted in forward operator")
    # while this repo saves the RAW batch variance and lets the grad
    # recompute rsqrt(v+eps).  batch_norm_grad's O@SavedVariance fast
    # path depends on this repo-local convention — keep the two sites
    # in sync if either changes.
    return {"Y": [y], "MeanOut": [mean_out], "VarianceOut": [var_out],
            "SavedMean": [saved_mean], "SavedVariance": [saved_var]}


@register_grad_kernel("batch_norm")
def batch_norm_grad(ctx, ins, attrs):
    """Closed-form BN backward (reference: batch_norm_op.cc
    BatchNormGradKernel — the same three-reduction formulation).

    Deliberately NOT jax.vjp of the forward: the vjp threads f32
    cotangents through the f32-upcast statistics path, and under the
    bf16-activation policy that emits ~4 full-size f32 tensors per BN
    (profiled via the StableHLO: 106 big bf16->f32 converts + 265 big
    f32 broadcasts across ResNet-50) — materialization bait that
    doubles the elementwise HBM bytes the policy exists to halve.
    Here every full-size operand stays in x's dtype: the two
    reductions accumulate in f32 with the converts fused into the
    sweep (same contract as _bn_stats), and dx is one affine
    ``A*dy + B*x + D`` whose per-channel f32 coefficients fold ALL
    statistics before a single downcast of [C]-sized vectors.

        g1 = sum(dy); g2 = sum(dy * (x - m)); inv = rsqrt(v + eps)
        A = scale*inv;  B = -scale*inv^3*g2/N;  D = -A*g1/N - B*m
        dscale = inv*g2; dbias = g1       (test mode: B = D = 0)
    """
    x = ins["X"][0]
    scale = ins["Scale"][0]
    dy = ins["OG@Y"][0]
    eps = attrs.get("epsilon", 1e-5)
    is_test = attrs.get("is_test", False)
    momentum = attrs.get("momentum", 0.9)
    layout = attrs.get("data_layout", "NCHW")

    axes, bshape = _bn_axes(x, layout)
    if is_test:
        m = ins["Mean"][0].astype(jnp.float32)
        v = ins["Variance"][0].astype(jnp.float32)
    else:
        # O@SavedVariance is the forward's RAW batch variance (repo
        # convention; the reference stores inverse-std here — see the
        # forward's save site above): the rsqrt(v+eps) below depends
        # on it, and reference tooling reading this slot must convert
        sm = _slot0(ins, "O@SavedMean")
        sv = _slot0(ins, "O@SavedVariance")
        if sm is not None and sv is not None:
            m, v = sm.astype(jnp.float32), sv.astype(jnp.float32)
        else:
            m, v = _bn_stats(x, axes)
    inv = jax.lax.rsqrt(v + eps)

    if dy is None:
        g1 = jnp.zeros_like(m)
        g2 = jnp.zeros_like(m)
    else:
        xs = x if x.dtype == jnp.float32 else x.astype(jnp.float32)
        dys = dy if dy.dtype == jnp.float32 else dy.astype(jnp.float32)
        g1 = jnp.sum(dys, axis=axes)
        g2 = jnp.sum(dys * (xs - m.reshape(bshape)), axis=axes)

    a = scale * inv
    n = 1
    for ax in axes:
        n *= x.shape[ax]
    if is_test:
        # running stats are nondiff inputs: only the Y path carries grad
        dx = jnp.zeros_like(x) if dy is None else \
            dy * a.reshape(bshape).astype(dy.dtype)
        return {"X@GRAD": [dx], "Scale@GRAD": [inv * g2],
                "Bias@GRAD": [g1]}

    b = -a * jnp.square(inv) * g2 / n
    d = -(a * g1) / n - b * m
    # cotangents through the statistic outputs: SavedMean/SavedVariance
    # are the batch stats, MeanOut/VarianceOut blend them with the
    # (nondiff) running stats at weight (1-momentum).  d mean/dx = 1/n,
    # d var/dx = 2(x-m)/n, so they fold into the same affine: one extra
    # per-channel term in b and d, no extra full-size pass.
    dm = _stat_cotangent(ins, "OG@SavedMean", "OG@MeanOut", momentum)
    dv = _stat_cotangent(ins, "OG@SavedVariance", "OG@VarianceOut",
                         momentum)
    if dv is not None:
        b = b + 2.0 * dv / n
        d = d - 2.0 * dv * m / n
    if dm is not None:
        d = d + dm / n
    if dy is None:
        dx = x * b.reshape(bshape).astype(x.dtype) + \
            d.reshape(bshape).astype(x.dtype)
    else:
        dx = (dy * a.reshape(bshape).astype(dy.dtype)
              + x * b.reshape(bshape).astype(x.dtype)
              + d.reshape(bshape).astype(x.dtype))
    return {"X@GRAD": [dx], "Scale@GRAD": [inv * g2], "Bias@GRAD": [g1]}


@register_op("layer_norm")
def layer_norm(ctx, ins, attrs):
    x = ins["X"][0]
    begin = int(attrs.get("begin_norm_axis", 1))
    eps = attrs.get("epsilon", 1e-5)
    lead = 1
    for d in x.shape[:begin]:
        lead *= d
    x2 = x.reshape(lead, -1)
    x2s = x2 if x2.dtype == jnp.float32 else x2.astype(jnp.float32)
    m = jnp.mean(x2s, axis=1, keepdims=True)
    v = jnp.var(x2s, axis=1, keepdims=True)
    norm = ((x2s - m) * jax.lax.rsqrt(v + eps)).astype(x.dtype)
    if "Scale" in ins:
        norm = norm * ins["Scale"][0].reshape(1, -1).astype(x.dtype)
    if "Bias" in ins:
        norm = norm + ins["Bias"][0].reshape(1, -1).astype(x.dtype)
    return {"Y": [norm.reshape(x.shape)], "Mean": [m.reshape(lead)],
            "Variance": [v.reshape(lead)]}


@register_grad_kernel("layer_norm")
def layer_norm_grad(ctx, ins, attrs):
    """Closed-form LN backward (reference: layer_norm_op.cc grad
    kernels) — same rationale as batch_norm_grad above: the generic
    vjp re-materializes the f32 statistics chain at full size under
    the bf16-activation policy; here the full-size math runs in x's
    dtype with per-row f32 coefficients (inv, the two row-reductions)
    folded before a single downcast.

        dy' = dy ⊙ scale;  g1 = Σ_j dy';  g2 = Σ_j dy'·(x-m)
        dx = dy'·inv + x·B + D,  B = -inv³·g2/N,  D = -inv·g1/N - B·m
        dscale_j = Σ_r dy·(x-m)·inv;  dbias_j = Σ_r dy
    """
    x = ins["X"][0]
    dy = ins["OG@Y"][0]
    begin = int(attrs.get("begin_norm_axis", 1))
    eps = attrs.get("epsilon", 1e-5)
    lead = 1
    for d in x.shape[:begin]:
        lead *= d
    x2 = x.reshape(lead, -1)
    n = x2.shape[1]

    xs = x2 if x2.dtype == jnp.float32 else x2.astype(jnp.float32)
    sm = _slot0(ins, "O@Mean")        # saved by the forward op
    sv = _slot0(ins, "O@Variance")
    if sm is not None and sv is not None:
        m = sm.reshape(lead, 1).astype(jnp.float32)
        v = sv.reshape(lead, 1).astype(jnp.float32)
    else:                             # pruned program: recompute (fuses)
        m = jnp.mean(xs, axis=1, keepdims=True)
        v = jnp.var(xs, axis=1, keepdims=True)
    inv = jax.lax.rsqrt(v + eps)
    xc = xs - m                       # f32, fuses into the reductions

    has_scale = "Scale" in ins
    scale = ins["Scale"][0].reshape(1, -1) if has_scale else None
    if dy is None:
        zrow = jnp.zeros((lead, 1), jnp.float32)
        g1, g2 = zrow, zrow
    else:
        dy2 = dy.reshape(lead, -1)
        dys = dy2 if dy2.dtype == jnp.float32 else dy2.astype(jnp.float32)
        dyp = dys * scale if has_scale else dys
        g1 = jnp.sum(dyp, axis=1, keepdims=True)
        g2 = jnp.sum(dyp * xc, axis=1, keepdims=True)

    b = -jnp.power(inv, 3) * g2 / n
    d = -inv * g1 / n - b * m
    # Mean/Variance output cotangents fold into the same per-row affine
    # (d mean/dx = 1/n, d var/dx = 2(x-m)/n) — no extra full-size pass
    dm = _slot0(ins, "OG@Mean")
    dv = _slot0(ins, "OG@Variance")
    if dv is not None:
        dv = dv.reshape(lead, 1).astype(jnp.float32)
        b = b + 2.0 * dv / n
        d = d - 2.0 * dv * m / n
    if dm is not None:
        d = d + dm.reshape(lead, 1).astype(jnp.float32) / n
    dx2 = x2 * b.astype(x2.dtype) + d.astype(x2.dtype)
    if dy is not None:
        dyp_lowp = (dy2 * scale.astype(dy2.dtype)) if has_scale else dy2
        dx2 = dx2 + dyp_lowp * inv.astype(dy2.dtype)
    out = {"X@GRAD": [dx2.reshape(x.shape)]}
    if has_scale:
        sg = jnp.sum(dys * xc * inv, axis=0) if dy is not None else \
            jnp.zeros(x2.shape[1], jnp.float32)
        out["Scale@GRAD"] = [sg]
    if "Bias" in ins:
        bg = jnp.sum(dys, axis=0) if dy is not None else \
            jnp.zeros(x2.shape[1], jnp.float32)
        out["Bias@GRAD"] = [bg]
    return out


@register_op("rms_norm", infer_shape=same_meta_infer_shape("X", "Y"))
def rms_norm(ctx, ins, attrs):
    """y = x * rsqrt(mean(x^2) + eps) * scale over the last axis (Zhang &
    Sennrich 2019), statistics and scaling in float32 whatever x's type,
    the result in x's type."""
    x = ins["X"][0]
    eps = attrs.get("epsilon", 1e-6)
    xs = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(jnp.square(xs), axis=-1, keepdims=True)
                        + eps)
    y = xs * inv * ins["Scale"][0].astype(jnp.float32)
    return {"Y": [y.astype(x.dtype)]}


@register_op("norm")
def norm(ctx, ins, attrs):
    """L2-normalize along axis (reference: norm_op.cc)."""
    x = ins["X"][0]
    axis = int(attrs.get("axis", -1))
    eps = attrs.get("epsilon", 1e-12)
    xs = x if x.dtype == jnp.float32 else x.astype(jnp.float32)
    n = jnp.sqrt(jnp.sum(jnp.square(xs), axis=axis, keepdims=True) + eps)
    return {"Out": [(xs / n).astype(x.dtype)]}


@register_op("one_hot", stop_gradient_op=True, nondiff_inputs=("X",))
def one_hot(ctx, ins, attrs):
    x = ins["X"][0]
    from ..core.ragged import RaggedTensor

    ragged = isinstance(x, RaggedTensor)
    ids = x.values if ragged else x
    depth = int(attrs["depth"])
    flat = jnp.reshape(ids, (-1,)).astype(jnp.int32)
    out = jax.nn.one_hot(flat, depth, dtype=jnp.float32)
    if ragged:
        return {"Out": [x.with_values(out)]}
    return {"Out": [out]}
