"""Optimizer update ops.

TPU-native equivalents of the reference optimizer ops (paddle/operators/
sgd_op.cc, momentum_op.cc, adam_op.cc, adamax_op.cc, adagrad_op.cc,
decayed_adagrad_op.cc, adadelta_op.cc, rmsprop_op.cc, ftrl_op.cc,
proximal_gd_op.cc, proximal_adagrad_op.cc).  Updates are pure functions;
the executor donates parameter buffers so XLA updates them in place.
Sparse (SelectedRows) gradients follow the reference's row-wise update
semantics (e.g. sgd_op.cc SelectedRows path) via scatter-add.

Every update op declares `in_place_outputs` (ParamOut aliases Param,
each state output aliases its state input) so the static analyzer's
alias/race detector (`paddle_tpu.analysis.dataflow`) can validate that
the aliased slots really name the same variable and that no concurrent
reader races the in-place write.
"""

import jax.numpy as jnp

from .registry import register_op
from ..core.ragged import SelectedRows

# every op type that advances a parameter in place: the one list the
# sharding rules, the sharding analyzer and the diagram read
UPDATE_OPS = frozenset([
    "sgd", "momentum", "adam", "adamax", "adagrad", "decayed_adagrad",
    "adadelta", "rmsprop", "ftrl", "proximal_gd", "proximal_adagrad"])


def _p(ins, slot):
    return ins[slot][0]


def _lr(ins):
    lr = ins["LearningRate"][0]
    return jnp.reshape(lr, ())


def _apply_update(param, delta_fn, grad):
    """delta_fn(p, g) -> new p.  Handles SelectedRows grads row-wise."""
    if isinstance(grad, SelectedRows):
        rows = grad.rows
        sub = param[rows]
        new_sub = delta_fn(sub, grad.values)
        return param.at[rows].set(new_sub)
    return delta_fn(param, grad)


@register_op("sgd", stop_gradient_op=True,
             in_place_outputs=("ParamOut",))
def sgd(ctx, ins, attrs):
    p, g, lr = _p(ins, "Param"), _p(ins, "Grad"), _lr(ins)
    if isinstance(g, SelectedRows):
        # reference sgd_op.cc SelectedRows path: scatter-sub the sparse rows
        out = p.at[g.rows].add(-lr * g.values)
    else:
        out = p - lr * g
    return {"ParamOut": [out]}


@register_op("momentum", stop_gradient_op=True,
             in_place_outputs=("ParamOut", "VelocityOut"))
def momentum(ctx, ins, attrs):
    p, g, v, lr = (_p(ins, "Param"), _p(ins, "Grad"),
                   _p(ins, "Velocity"), _lr(ins))
    mu = attrs["mu"]
    if isinstance(g, SelectedRows):
        g = g.to_dense()
    v_out = mu * v + g
    if attrs.get("use_nesterov", False):
        p_out = p - (g + mu * v_out) * lr
    else:
        p_out = p - lr * v_out
    return {"ParamOut": [p_out], "VelocityOut": [v_out]}


@register_op("adam", stop_gradient_op=True,
             in_place_outputs=("ParamOut", "Moment1Out", "Moment2Out"))
def adam(ctx, ins, attrs):
    p, g, lr = _p(ins, "Param"), _p(ins, "Grad"), _lr(ins)
    m1, m2 = _p(ins, "Moment1"), _p(ins, "Moment2")
    b1p = jnp.reshape(_p(ins, "Beta1Pow"), ())
    b2p = jnp.reshape(_p(ins, "Beta2Pow"), ())
    b1 = attrs.get("beta1", 0.9)
    b2 = attrs.get("beta2", 0.999)
    eps = attrs.get("epsilon", 1e-8)
    if isinstance(g, SelectedRows):
        g = g.to_dense()
    m1_out = b1 * m1 + (1 - b1) * g
    m2_out = b2 * m2 + (1 - b2) * jnp.square(g)
    lr_t = lr * jnp.sqrt(1 - b2p) / (1 - b1p)
    p_out = p - lr_t * m1_out / (jnp.sqrt(m2_out) + eps)
    return {"ParamOut": [p_out], "Moment1Out": [m1_out],
            "Moment2Out": [m2_out]}


@register_op("adamax", stop_gradient_op=True,
             in_place_outputs=("ParamOut", "MomentOut", "InfNormOut"))
def adamax(ctx, ins, attrs):
    p, g, lr = _p(ins, "Param"), _p(ins, "Grad"), _lr(ins)
    m, inf = _p(ins, "Moment"), _p(ins, "InfNorm")
    b1p = jnp.reshape(_p(ins, "Beta1Pow"), ())
    b1 = attrs.get("beta1", 0.9)
    b2 = attrs.get("beta2", 0.999)
    eps = attrs.get("epsilon", 1e-8)
    if isinstance(g, SelectedRows):
        g = g.to_dense()
    m_out = b1 * m + (1 - b1) * g
    inf_out = jnp.maximum(b2 * inf, jnp.abs(g))
    lr_t = lr / (1 - b1p)
    p_out = p - lr_t * m_out / (inf_out + eps)
    return {"ParamOut": [p_out], "MomentOut": [m_out],
            "InfNormOut": [inf_out]}


@register_op("adagrad", stop_gradient_op=True,
             in_place_outputs=("ParamOut", "MomentOut"))
def adagrad(ctx, ins, attrs):
    p, g, lr = _p(ins, "Param"), _p(ins, "Grad"), _lr(ins)
    mom = _p(ins, "Moment")
    eps = attrs.get("epsilon", 1e-6)
    if isinstance(g, SelectedRows):
        # reference adagrad_op SelectedRows path
        mom_out = mom.at[g.rows].add(jnp.square(g.values))
        p_out = p.at[g.rows].add(
            -jnp.reshape(lr, ()) * g.values /
            (jnp.sqrt(mom_out[g.rows]) + eps))
        return {"ParamOut": [p_out], "MomentOut": [mom_out]}
    mom_out = mom + jnp.square(g)
    p_out = p - lr * g / (jnp.sqrt(mom_out) + eps)
    return {"ParamOut": [p_out], "MomentOut": [mom_out]}


@register_op("decayed_adagrad", stop_gradient_op=True,
             in_place_outputs=("ParamOut", "MomentOut"))
def decayed_adagrad(ctx, ins, attrs):
    p, g, lr = _p(ins, "Param"), _p(ins, "Grad"), _lr(ins)
    mom = _p(ins, "Moment")
    decay = attrs.get("decay", 0.95)
    eps = attrs.get("epsilon", 1e-6)
    if isinstance(g, SelectedRows):
        g = g.to_dense()
    mom_out = decay * mom + (1 - decay) * jnp.square(g)
    p_out = p - lr * g / (jnp.sqrt(mom_out) + eps)
    return {"ParamOut": [p_out], "MomentOut": [mom_out]}


@register_op("adadelta", stop_gradient_op=True,
             in_place_outputs=("ParamOut", "AvgSquaredGradOut",
                               "AvgSquaredUpdateOut"))
def adadelta(ctx, ins, attrs):
    p, g = _p(ins, "Param"), _p(ins, "Grad")
    avg_sq_g = _p(ins, "AvgSquaredGrad")
    avg_sq_u = _p(ins, "AvgSquaredUpdate")
    rho = attrs.get("rho", 0.95)
    eps = attrs.get("epsilon", 1e-6)
    if isinstance(g, SelectedRows):
        g = g.to_dense()
    asg_out = rho * avg_sq_g + (1 - rho) * jnp.square(g)
    update = -jnp.sqrt((avg_sq_u + eps) / (asg_out + eps)) * g
    asu_out = rho * avg_sq_u + (1 - rho) * jnp.square(update)
    return {"ParamOut": [p + update], "AvgSquaredGradOut": [asg_out],
            "AvgSquaredUpdateOut": [asu_out]}


@register_op("rmsprop", stop_gradient_op=True,
             in_place_outputs=("ParamOut", "MomentOut",
                               "MeanSquareOut"))
def rmsprop(ctx, ins, attrs):
    p, g, lr = _p(ins, "Param"), _p(ins, "Grad"), _lr(ins)
    ms, mom = _p(ins, "MeanSquare"), _p(ins, "Moment")
    rho = attrs.get("decay", 0.9)
    eps = attrs.get("epsilon", 1e-10)
    mu = attrs.get("momentum", 0.0)
    if isinstance(g, SelectedRows):
        g = g.to_dense()
    ms_out = rho * ms + (1 - rho) * jnp.square(g)
    mom_out = mu * mom + lr * g / jnp.sqrt(ms_out + eps)
    return {"ParamOut": [p - mom_out], "MomentOut": [mom_out],
            "MeanSquareOut": [ms_out]}


@register_op("ftrl", stop_gradient_op=True,
             in_place_outputs=("ParamOut", "SquaredAccumOut",
                               "LinearAccumOut"))
def ftrl(ctx, ins, attrs):
    p, g, lr = _p(ins, "Param"), _p(ins, "Grad"), _lr(ins)
    sq_accum, lin_accum = _p(ins, "SquaredAccumulator"), \
        _p(ins, "LinearAccumulator")
    l1 = attrs.get("l1", 0.0)
    l2 = attrs.get("l2", 0.0)
    lr_power = attrs.get("lr_power", -0.5)
    if isinstance(g, SelectedRows):
        g = g.to_dense()
    new_accum = sq_accum + jnp.square(g)
    if lr_power == -0.5:
        sigma = (jnp.sqrt(new_accum) - jnp.sqrt(sq_accum)) / lr
    else:
        sigma = (jnp.power(new_accum, -lr_power) -
                 jnp.power(sq_accum, -lr_power)) / lr
    lin_out = lin_accum + g - sigma * p
    if lr_power == -0.5:
        denom = jnp.sqrt(new_accum) / lr + 2 * l2
    else:
        denom = jnp.power(new_accum, -lr_power) / lr + 2 * l2
    pre_shrink = (l1 * jnp.sign(lin_out) - lin_out) / denom
    p_out = jnp.where(jnp.abs(lin_out) > l1, pre_shrink,
                      jnp.zeros_like(p))
    return {"ParamOut": [p_out], "SquaredAccumOut": [new_accum],
            "LinearAccumOut": [lin_out]}


@register_op("proximal_gd", stop_gradient_op=True,
             in_place_outputs=("ParamOut",))
def proximal_gd(ctx, ins, attrs):
    p, g, lr = _p(ins, "Param"), _p(ins, "Grad"), _lr(ins)
    l1 = attrs.get("l1", 0.0)
    l2 = attrs.get("l2", 0.0)
    if isinstance(g, SelectedRows):
        g = g.to_dense()
    prox = p - lr * g
    p_out = (jnp.sign(prox) / (1.0 + lr * l2) *
             jnp.maximum(jnp.abs(prox) - lr * l1, 0.0))
    return {"ParamOut": [p_out]}


@register_op("proximal_adagrad", stop_gradient_op=True,
             in_place_outputs=("ParamOut", "MomentOut"))
def proximal_adagrad(ctx, ins, attrs):
    p, g, lr = _p(ins, "Param"), _p(ins, "Grad"), _lr(ins)
    mom = _p(ins, "Moment")
    l1 = attrs.get("l1", 0.0)
    l2 = attrs.get("l2", 0.0)
    if isinstance(g, SelectedRows):
        g = g.to_dense()
    mom_out = mom + jnp.square(g)
    lr_t = lr / jnp.sqrt(mom_out)
    prox = p - lr_t * g
    p_out = (jnp.sign(prox) / (1.0 + lr_t * l2) *
             jnp.maximum(jnp.abs(prox) - lr_t * l1, 0.0))
    return {"ParamOut": [p_out], "MomentOut": [mom_out]}
