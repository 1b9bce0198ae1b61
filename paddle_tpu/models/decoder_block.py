"""What the decoder builders share (`looped_program.py`,
`moe_program.py`, and the cached steps `latent_moe_program.py`,
`window_moe_program.py` and `linear_moe_program.py`): the post-2023
block's bias-free projection, its RMSNorm with a named scale, its
attention sub-layer, the feed-forward half of one chip's share of an
expert model, and what a cached step that takes a block of positions
reads off its token feed, from `fluid.layers` alone.  Every parameter is
created by the name it is given, so a builder decides what is shared."""

import numpy as np

from .. import fluid
from ..fluid.layer_helper import LayerHelper
from ..fluid.param_attr import ParamAttr

__all__ = ["linear", "linear_float32", "norm", "head_norm", "attention",
           "gated_feed_forward",
           "share_feed_forward", "token_feeds", "head_cross_entropy",
           "block_positions", "last", "last_token_rows"]


# below every value a 16-bit float holds: a clip with no floor
_NO_FLOOR = -3e38


def linear(x, size, name):
    return fluid.layers.fc(input=x, size=size, num_flatten_dims=2,
                           param_attr=ParamAttr(name=name), bias_attr=False)


def linear_float32(x, size, name):
    """`linear` with the product in float32 whatever the weight's type
    (the `mul` op's `float32`: neither operand is rounded on its way to
    the matrix unit), the result float32: a head whose logits are asked
    for in float32 (`enable_lm_head_fp32`).  `x` is cast up first; the
    parameter is declared as `linear` declares it and may be served in a
    narrower type, which is read as it lies."""
    helper = LayerHelper("fc", param_attr=ParamAttr(name=name))
    w = helper.create_parameter(helper.param_attr,
                                shape=[int(x.shape[-1]), size],
                                dtype=x.dtype)
    out = helper.create_tmp_variable("float32")
    helper.append_op(
        type="mul", inputs={"X": [fluid.layers.cast(x, "float32")],
                            "Y": [w]},
        outputs={"Out": [out]},
        attrs={"x_num_col_dims": 2, "y_num_col_dims": 1, "float32": True})
    return out


def norm(x, eps, name):
    return fluid.layers.rms_norm(x, epsilon=eps,
                                 param_attr=ParamAttr(name=name))


def head_norm(t, heads, d_head, eps, name):
    """RMSNorm over each head's `d_head` values of t [batch, T, heads *
    d_head], one learned [d_head] scale `name` for every head (the
    Qwen3 block's q and k norms)."""
    t = norm(fluid.layers.reshape(t, [0, 0, heads, d_head]), eps, name)
    return fluid.layers.reshape(t, [0, 0, heads * d_head])


def token_feeds(batch, seq_len):
    """The data layers (tokens, positions, targets) of a decoder's
    training program: int64 [batch, seq_len] twice and [batch, seq_len,
    1], as `transformer_program_feeds` feeds them."""
    return tuple(
        fluid.layers.data(name=name, shape=shape, dtype="int64",
                          append_batch_size=False)
        for name, shape in (("tokens", [batch, seq_len]),
                            ("positions", [batch, seq_len]),
                            ("targets", [batch, seq_len, 1])))


def head_cross_entropy(x, targets, eps, norm_name, head_name, vocab_size):
    """(logits [batch, seq, vocab], mean cross-entropy) of the final
    RMSNorm `norm_name` and the untied head `head_name` over `x`."""
    logits = linear(norm(x, eps, norm_name), vocab_size, head_name)
    ce = fluid.layers.mean(x=fluid.layers.softmax_with_cross_entropy(
        fluid.layers.reshape(x=logits, shape=[-1, vocab_size]),
        fluid.layers.reshape(x=targets, shape=[-1, 1])))
    return logits, ce


def block_positions(tok, pos, batch):
    """(ones, positions) of a cached step whose token feed `tok` is
    declared [batch, -1], T >= 1 consecutive tokens of every row from
    position `pos` int64 [batch] on.  T is read off the feed: `ones`
    int64 [1, T], a one a position of the block, counted before each
    for its offset (`positions` [batch, T] = pos .. pos + T - 1) and all
    together for the advance (`pos + reduce_sum(ones)`)."""
    ones = fluid.layers.fill_constant_batch_size_like(
        tok, shape=[1, 1], dtype="int64", value=1, input_dim_idx=1,
        output_dim_idx=1)
    return ones, fluid.layers.reshape(x=pos, shape=[batch, 1]) \
        + fluid.layers.cumsum(ones, axis=1, exclusive=True)


def last(t):
    """[batch, T, ...] -> [batch, 1, ...]: the block's last position.
    What a block-taking step hands a decoder of itself (its `parts`, the
    head's input) is of that position, in shapes T does not change: a
    carry keeps its shape.  At T = 1 the slice is the identity."""
    return fluid.layers.slice(t, axes=[1], starts=[-1], ends=[2 ** 31 - 1])


def last_token_rows(ones, batch):
    """`last_row(t)`: [batch * T, k], a token a row as a router has
    them, a row of the batch after a row -> [batch, k], each row's last
    token: row b * T + T - 1, with T read off `ones`
    (`block_positions`).  Gathered, not cut out of a reshape to [batch,
    T, k]: the token axis is open, and shape inference stands 840 in for
    it, which a batch of 128 does not divide."""
    last_token = fluid.layers.assign(
        np.arange(1, batch + 1, dtype="int64").reshape(batch, 1),
        fluid.layers.create_tensor("int64")) \
        * last(fluid.layers.cumsum(ones, axis=1)) - last(ones)
    return lambda t: fluid.layers.gather(t, last_token)


def _repeat_heads(x, n_kv_head, times, d_head):
    """[batch, seq, n_kv_head * d_head] -> [batch, seq, n_kv_head * times
    * d_head], each key/value head `times` times in a row, so that query
    head i reads key/value head i // times: reshape, `expand` on a new
    axis, reshape.  The copies are the Program's; the gradient of
    `expand` sums a group's heads."""
    batch, seq = x.shape[0], x.shape[1]
    x = fluid.layers.reshape(x, [batch, seq, n_kv_head, 1, d_head])
    x = fluid.layers.expand(x, [1, 1, 1, times, 1])
    return fluid.layers.reshape(x, [batch, seq, n_kv_head * times * d_head])


def attention(h, positions, names, n_head, d_head, theta, qk_norm_eps=None,
              n_kv_head=None, sm_scale=None, window=0):
    """Causal self-attention over `h` [batch, seq, hidden], already
    normed: projections `names["wq"|"wk"|"wv"]`, rotary positions
    (rotate-half over each head, base `theta`; none where `theta` is
    None, and `positions` is then not read), the `flash_attention`
    op, the projection `names["wo"]` back to hidden.  With
    `qk_norm_eps`, q and k are RMS-normed over their whole projection
    (`names["q_norm"|"k_norm"]`) before they are split into heads and
    rotated, as OLMoE does.  With `n_kv_head` fewer than `n_head`, k and
    v are projected to that many heads and each is repeated for its
    group of query heads.  `sm_scale` scales the scores (default
    1 / sqrt(d_head)).  With `window` W > 0 a query attends its last W
    positions alone (the op's `window`: the flash kernels fold no chunk
    of keys wholly before them, forward or backward)."""
    n_kv_head = n_kv_head or n_head
    q = linear(h, n_head * d_head, names["wq"])
    k, v = (linear(h, n_kv_head * d_head, names[w]) for w in ("wk", "wv"))
    if qk_norm_eps is not None:
        q = norm(q, qk_norm_eps, names["q_norm"])
        k = norm(k, qk_norm_eps, names["k_norm"])
    if theta is not None:
        q = fluid.layers.rope(q, positions, n_head, theta)
        k = fluid.layers.rope(k, positions, n_kv_head, theta)
    if n_kv_head != n_head:
        k, v = (_repeat_heads(t, n_kv_head, n_head // n_kv_head, d_head)
                for t in (k, v))
    o = fluid.layers.flash_attention(q, k, v, num_heads=n_head, causal=True,
                                     sm_scale=sm_scale, window=window)
    return linear(o, h.shape[-1], names["wo"])


def gated_feed_forward(u, width, names, limit=None, name=None):
    """(silu(g) * v) W_out with [g | v] = u W_in, gate and up in one
    [hidden, 2 * width] matrix `names["w_in"]`, the first `width`
    columns the gate; `names["w_out"]` back to hidden.  With `limit` L
    the clamped form, silu(min(g, L)) * clip(v, -L, L).  `name` names
    the ops between the two products (the instances in a trace start
    with it)."""
    named = {"name": name} if name else {}
    gate, up = fluid.layers.split(
        linear(u, 2 * width, names["w_in"]), 2, dim=-1, **named)
    if limit:
        gate = fluid.layers.clip(gate, min=_NO_FLOOR, max=float(limit))
        up = fluid.layers.clip(up, min=-float(limit), max=float(limit))
    act = fluid.layers.elementwise_mul(
        fluid.layers.swish(gate, **named), up, **named) if name \
        else fluid.layers.swish(gate) * up
    return linear(act, u.shape[-1], names["w_out"])


def share_feed_forward(u, block, dense, d_ff, d_expert, n_experts, held,
                       top_k, norm_topk, routed_scale, router_bias=False,
                       n_group=0, topk_group=0, scoring="sigmoid",
                       shared_gate=None, swiglu_limit=None, dense_name=None,
                       d_shared=None):
    """The feed-forward half of one layer of one chip's share of an
    expert model, for u [batch, seq, hidden], already normed; `block`
    names the layer's parameters.  Returns (F(u), routing).  The
    defaults are the sigmoid-routed layer of the DeepSeek-V3 family
    (which openPangu-Ultra-MoE, DeepSeek-V3.2 and K-EXAONE carry to the
    number); `scoring="softmax"` routes by a softmax over all
    `n_experts` scored (Qwen3-Next's: the chosen probabilities divided
    by their sum under `norm_topk`, over the held range as over the
    whole), and `shared_gate` names a [hidden, 1] parameter whose
    sigmoid(u w) multiplies the shared expert's output (a gate on the
    shared expert, one scalar a token).

    `dense`: the gated feed-forward of width `d_ff` (`ffn_in`,
    `ffn_out`; `dense_name` names its ops between the two products), and
    `routing` is None.  Otherwise a shared expert of
    width `d_shared` (`shared_in`, `shared_out`; default `d_expert`, the
    routed experts': granite-4.0-h-small's is twice theirs) beside a
    routed layer
    (`fluid.layers.moe`: sigmoid scores over `n_experts`, `top_k` a
    token chosen by score plus `router_bias` inside the best
    `topk_group` of `n_group` groups, the chosen weights normalised and
    times `routed_scale`) that holds the experts `held` = (first,
    count) of those its router scores; `routing` is {"top_w",
    "top_idx", "counts": the router's Variables, "moe_in": u, "moe_out":
    the held experts' part}.  `swiglu_limit` clamps every gated
    feed-forward here, dense, shared and routed (`gated_feed_forward`).
    A `block` that names no `shared_in` has no
    shared expert (Keye-VL-2.0's: softmax experts alone), and F(u) is
    the held experts' part."""
    if dense:
        return gated_feed_forward(
            u, d_ff, {"w_in": block["ffn_in"], "w_out": block["ffn_out"]},
            swiglu_limit, dense_name), None
    m, _, _, routing = fluid.layers.moe(
        u, n_experts, d_expert, top_k,
        *(ParamAttr(name=block[w])
          for w in ("router", "w_gate", "w_up", "w_down")),
        scoring=scoring, norm_topk=norm_topk, scale=routed_scale,
        held=held,
        bias_attr=ParamAttr(name=block["router_bias"])
        if router_bias else None,
        n_group=n_group, topk_group=topk_group, swiglu_limit=swiglu_limit)
    f = m
    if "shared_in" in block:
        shared = gated_feed_forward(
            u, d_shared or d_expert, {"w_in": block["shared_in"],
                          "w_out": block["shared_out"]}, swiglu_limit)
        if shared_gate is not None:
            # named: the ops' instances in a trace start with it
            shared = fluid.layers.elementwise_mul(
                shared, fluid.layers.sigmoid(linear(u, 1, shared_gate),
                                             name="shared_gate"),
                name="shared_gate")
        f = shared + m
    return f, dict({key: routing[key]
                    for key in ("top_w", "top_idx", "counts")},
                   moe_in=u, moe_out=m)
