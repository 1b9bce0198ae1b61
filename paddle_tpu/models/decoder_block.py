"""What the decoder builders share (`looped_program.py`,
`moe_program.py`): the post-2023 block's bias-free projection, its
RMSNorm with a named scale, and its attention sub-layer, from
`fluid.layers` alone.  Every parameter is created by the name it is
given, so a builder decides what is shared."""

from .. import fluid
from ..fluid.param_attr import ParamAttr

__all__ = ["linear", "norm", "attention"]


def linear(x, size, name):
    return fluid.layers.fc(input=x, size=size, num_flatten_dims=2,
                           param_attr=ParamAttr(name=name), bias_attr=False)


def norm(x, eps, name):
    return fluid.layers.rms_norm(x, epsilon=eps,
                                 param_attr=ParamAttr(name=name))


def attention(h, positions, names, n_head, d_head, theta, qk_norm_eps=None):
    """Causal self-attention over `h` [batch, seq, hidden], already
    normed: projections `names["wq"|"wk"|"wv"]`, rotary positions
    (rotate-half over each head, base `theta`), the `flash_attention`
    op, the projection `names["wo"]` back to hidden.  With
    `qk_norm_eps`, q and k are RMS-normed over their whole projection
    (`names["q_norm"|"k_norm"]`) before they are split into heads and
    rotated, as OLMoE does."""
    q, k, v = (linear(h, n_head * d_head, names[w])
               for w in ("wq", "wk", "wv"))
    if qk_norm_eps is not None:
        q = norm(q, qk_norm_eps, names["q_norm"])
        k = norm(k, qk_norm_eps, names["k_norm"])
    o = fluid.layers.flash_attention(
        fluid.layers.rope(q, positions, n_head, theta),
        fluid.layers.rope(k, positions, n_head, theta), v,
        num_heads=n_head, causal=True)
    return linear(o, h.shape[-1], names["wo"])
