"""A residual of several streams mixed through learned mappings:
manifold-constrained hyper-connections (arXiv:2512.24880), the form
Hy4-preview's `hc_mult` streams are written from.

A token's residual is X [n, d], n streams of the hidden width, and around
every sub-layer F (with parameters of the sub-layer's own)

    x~    = RMSNorm(vec(X))                      [n d], no learned scale
    Hpre  = sigmoid(a_pre  (x~ P_pre)  + b_pre)              [n]
    Hpost = magnitude sigmoid(a_post (x~ P_post) + b_post)   [n]
    Hres  = SK(exp(a_res mat(x~ P_res) + b_res))             [n, n]
    u     = sum_j Hpre[j] X_j ;  y = F(u)
    X'_i  = sum_j Hres[i, j] X_j + Hpost[i] y

SK is Sinkhorn-Knopp: `iterations` times the rows over their sums, then
the columns over theirs, which brings a positive matrix to the doubly
stochastic ones (the manifold: such a mix keeps the streams' mean and
cannot grow the residual however many layers it passes).

Three ops, each under the scope `hyper_connection`, so that a trace
says what the residual's mixing costs a step:

- `hc_maps`: X, the three projections side by side P [n d, n n + 2 n]
  (`pre | post | res`), Alpha [3] and Bias [n n + 2 n] in the same order
  -> Pre [.., n], Post [.., n], Res [.., n, n], float32.
- `hc_pre`: X, Pre -> U [.., d], the sub-layer's input.
- `hc_post`: X, Res, Post, Y -> XOut [.., n, d].

All arithmetic is float32 whatever the streams' type (the mappings are
a few dozen numbers a token, and a Sinkhorn iteration divides by sums of
them); the product with P is at the highest precision; U and XOut come
back in X's type.  No gradient: the streams are a serving path's so far.
"""

import numpy as np

import jax
import jax.numpy as jnp

from .registry import register_op

SCOPE = "hyper_connection"


def _set_meta(block, name, shape, dtype):
    desc = block.var_recursive(name).desc
    desc.shape, desc.dtype, desc.lod_level = tuple(shape), dtype, 0


def _maps_infer_shape(block, op_desc):
    x = block.var_recursive(op_desc.input("X")[0]).desc
    lead, n = tuple(x.shape[:-2]), x.shape[-2]
    for slot, tail in (("Pre", (n,)), ("Post", (n,)), ("Res", (n, n))):
        _set_meta(block, op_desc.output(slot)[0], lead + tail, "float32")


def sinkhorn(m, iterations):
    """`iterations` times: rows over their sums, columns over theirs."""
    for _ in range(iterations):
        m = m / jnp.sum(m, axis=-1, keepdims=True)
        m = m / jnp.sum(m, axis=-2, keepdims=True)
    return m


@register_op("hc_maps", stop_gradient_op=True, infer_shape=_maps_infer_shape)
def hc_maps(ctx, ins, attrs):
    """The three mappings of one sub-layer's hyper-connection from the
    streams X [.., n, d] (the module's docstring)."""
    x, p = ins["X"][0], ins["P"][0]
    alpha, bias = ins["Alpha"][0], ins["Bias"][0]
    n, d = x.shape[-2:]
    if p.shape != (n * d, n * n + 2 * n) or bias.shape != (n * n + 2 * n,) \
            or alpha.shape != (3,):
        raise ValueError(
            "hc_maps: %d streams of %d want P [%d, %d], Alpha [3] and Bias "
            "[%d], got %s, %s and %s"
            % (n, d, n * d, n * n + 2 * n, n * n + 2 * n, p.shape,
               alpha.shape, bias.shape))
    f32 = jnp.float32
    with jax.named_scope(SCOPE):
        flat = x.reshape(x.shape[:-2] + (n * d,)).astype(f32)
        flat = flat * jax.lax.rsqrt(
            jnp.mean(jnp.square(flat), axis=-1, keepdims=True)
            + float(attrs.get("epsilon", 1e-6)))
        z = jnp.matmul(flat, p.astype(f32),
                       precision=jax.lax.Precision.HIGHEST)
        # a column's scalar: a_pre, a_post or a_res
        of = np.repeat(np.arange(3), [n, n, n * n])
        z = z * alpha.astype(f32)[of] + bias.astype(f32)
        pre = jax.nn.sigmoid(z[..., :n])
        post = float(attrs.get("magnitude", 2.0)) \
            * jax.nn.sigmoid(z[..., n:2 * n])
        res = sinkhorn(jnp.exp(z[..., 2 * n:]).reshape(z.shape[:-1] + (n, n)),
                       int(attrs.get("iterations", 20)))
    return {"Pre": [pre], "Post": [post], "Res": [res]}


def _pre_infer_shape(block, op_desc):
    x = block.var_recursive(op_desc.input("X")[0]).desc
    _set_meta(block, op_desc.output("U")[0],
              tuple(x.shape[:-2]) + (x.shape[-1],), x.dtype)


@register_op("hc_pre", stop_gradient_op=True, infer_shape=_pre_infer_shape)
def hc_pre(ctx, ins, attrs):
    """U = sum_j Pre[j] X_j: the streams X [.., n, d] read into one
    sub-layer input [.., d], summed in float32, in X's type."""
    x, pre = ins["X"][0], ins["Pre"][0]
    with jax.named_scope(SCOPE):
        u = jnp.sum(pre.astype(jnp.float32)[..., None]
                    * x.astype(jnp.float32), axis=-2).astype(x.dtype)
    return {"U": [u]}


def _post_infer_shape(block, op_desc):
    x = block.var_recursive(op_desc.input("X")[0]).desc
    _set_meta(block, op_desc.output("XOut")[0], x.shape, x.dtype)


@register_op("hc_post", stop_gradient_op=True, infer_shape=_post_infer_shape)
def hc_post(ctx, ins, attrs):
    """XOut_i = sum_j Res[i, j] X_j + Post[i] Y: the streams mixed among
    themselves and the sub-layer's output Y [.., d] written to each, in
    float32 (a 4 x 4 mix a token: multiply-adds on the vector unit, no
    matrix product), in X's type."""
    x, y = ins["X"][0], ins["Y"][0]
    res, post = ins["Res"][0], ins["Post"][0]
    f32 = jnp.float32
    with jax.named_scope(SCOPE):
        mixed = jnp.sum(res.astype(f32)[..., None]
                        * x.astype(f32)[..., None, :, :], axis=-2)
        out = (mixed + post.astype(f32)[..., None]
               * y.astype(f32)[..., None, :]).astype(x.dtype)
    return {"XOut": [out]}
