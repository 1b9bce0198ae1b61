"""A state-space / attention hybrid decoder as a fluid Program:
granite-4.0-h-micro (huggingface.co/ibm-granite/granite-4.0-h-micro,
`model_type` granitemoehybrid; the state-space mixer is Mamba-2,
arXiv:2405.21060).

`layer_types` says, layer by layer, whether the mixer is "mamba" (one
projection to [z | xBC | dt], `causal_conv1d` with SiLU over xBC, the
`ssd_scan` op over [x | B | C] with one group, the result gated by
silu(z) and RMS-normed over its whole width, a projection back) or
"attention" (the block of `decoder_block.py` with grouped key/value
heads, no positions and the model's own softmax scale).  Every mixer is
followed by a gated-SiLU feed-forward whose gate and up are one matrix;
both sub-layers are pre-normed and enter the residual stream times
`residual_multiplier`.  The embedding is scaled by
`embedding_multiplier` and is also the head (tied: `lookup_table` and,
through a `transpose` that XLA folds into the product, `matmul` on one
parameter, whose two gradients `append_backward` adds), the logits
divided by `logits_scaling`.  The
equations are in `models/reference/granite_hybrid.py`, which the tests
hold this to.
"""

from .. import fluid
from ..fluid.param_attr import ParamAttr
from .decoder_block import attention, gated_feed_forward, linear, norm

__all__ = ["build_granite_hybrid_program", "granite_hybrid_param_names"]

_MIXER_PARAMS = {
    "mamba": ("in_proj", "conv_w", "conv_b", "dt_bias", "a_log", "d",
              "norm_g", "out_proj"),
    "attention": ("wq", "wk", "wv", "wo"),
}
_SHARED_PARAMS = ("norm_1", "norm_2", "w_in", "w_out")


def granite_hybrid_param_names(layer_types):
    """The parameters' names, laid out as the reference's `params`."""
    return {
        "embed": "embed.w",
        "blocks": [{w: "block_%d.%s" % (i, w)
                    for w in _MIXER_PARAMS[kind] + _SHARED_PARAMS}
                   for i, kind in enumerate(layer_types)],
        "norm_f": "norm_f",
    }


def _mamba_mixer(h, names, n_heads, d_head, d_state, d_conv, chunk, eps):
    inner = n_heads * d_head
    z, xbc, dt = fluid.layers.split(
        linear(h, 2 * inner + 2 * d_state + n_heads, names["in_proj"]),
        [inner, inner + 2 * d_state, n_heads], dim=-1)
    xbc = fluid.layers.causal_conv1d(
        xbc, filter_size=d_conv, activation="silu",
        param_attr=ParamAttr(name=names["conv_w"]),
        bias_attr=ParamAttr(name=names["conv_b"]))
    x, b, c = fluid.layers.split(xbc, [inner, d_state, d_state], dim=-1)
    y = fluid.layers.ssd_scan(
        x, dt, b, c, n_heads, chunk_size=chunk,
        a_log_attr=ParamAttr(name=names["a_log"]),
        d_attr=ParamAttr(name=names["d"]),
        dt_bias_attr=ParamAttr(name=names["dt_bias"]))
    y = norm(y * fluid.layers.swish(z), eps, names["norm_g"])
    return linear(y, h.shape[-1], names["out_proj"])


def build_granite_hybrid_program(
        batch, seq_len, vocab_size, layer_types=("mamba", "attention"),
        d_model=64, d_ff=128, n_head=4, n_kv_head=2, d_head=None,
        mamba_heads=4, mamba_d_head=32, d_state=16, d_conv=4, chunk=8,
        eps=1e-5, sm_scale=None, embedding_multiplier=1.0,
        residual_multiplier=1.0, logits_scaling=1.0):
    """Returns (main, startup, avg_loss, parts): `parts` holds the
    Variables "logits" [batch, seq, vocab] and, in lists, "mixer_out"
    (every layer's mixer output before the residual multiplier).

    Feeds: tokens int64 [batch, seq_len], targets int64 [batch, seq_len,
    1]; the model has no positions.
    """
    d_head = d_head or d_model // n_head
    names = granite_hybrid_param_names(layer_types)
    main = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(main, startup):
        tokens = fluid.layers.data(
            name="tokens", shape=[batch, seq_len], dtype="int64",
            append_batch_size=False)
        targets = fluid.layers.data(
            name="targets", shape=[batch, seq_len, 1], dtype="int64",
            append_batch_size=False)

        embed = ParamAttr(name=names["embed"])
        x = fluid.layers.scale(
            fluid.layers.embedding(tokens, size=[vocab_size, d_model],
                                   param_attr=embed),
            scale=float(embedding_multiplier))
        parts = {"mixer_out": []}
        for kind, block in zip(layer_types, names["blocks"]):
            h = norm(x, eps, block["norm_1"])
            if kind == "mamba":
                m = _mamba_mixer(h, block, mamba_heads, mamba_d_head,
                                 d_state, d_conv, chunk, eps)
            elif kind == "attention":
                m = attention(h, None, block, n_head, d_head, None,
                              n_kv_head=n_kv_head, sm_scale=sm_scale)
            else:
                raise ValueError("layer type %r (mamba or attention)" % kind)
            parts["mixer_out"].append(m)
            x = x + fluid.layers.scale(m, scale=float(residual_multiplier))
            f = gated_feed_forward(norm(x, eps, block["norm_2"]), d_ff,
                                   block)
            x = x + fluid.layers.scale(f, scale=float(residual_multiplier))

        table = main.global_block().var(names["embed"])
        logits = fluid.layers.scale(
            fluid.layers.matmul(norm(x, eps, names["norm_f"]),
                                fluid.layers.transpose(table, [1, 0])),
            scale=1.0 / float(logits_scaling))
        avg_loss = fluid.layers.mean(
            x=fluid.layers.softmax_with_cross_entropy(
                fluid.layers.reshape(x=logits, shape=[-1, vocab_size]),
                fluid.layers.reshape(x=targets, shape=[-1, 1])))
        parts["logits"] = logits
    return main, startup, avg_loss, parts
