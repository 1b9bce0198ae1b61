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

**Served** (`build_granite_hybrid_cached_step_program`): one chip's
share of granite-4.0-h-small
(huggingface.co/ibm-granite/granite-4.0-h-small: the same family with
routed experts) as a cached decode step Program, a block of T >= 1
consecutive tokens of every row in (T = 1: a decode step; a prompt's
prefill feeds a chunk of the scan an application), the logits after the
block's last out, over the rows of the tied table the chip holds.  The
same `_mamba_mixer`, **carrying two states a layer**: the last `d_conv -
1` positions of its convolution's input ("conv_tail_<i>" [batch, d_conv
- 1, heads * d_head + 2 * d_state], in the weights' type) and the
scan's state ("ssd_state_<i>" [batch, d_state, heads * d_head] float32,
the kernels' own layout: ops/ssm.py), which a step rewrites whole
through `ssd_scan`'s `State` (one position through
kernels/ssd_step.py's `ssd_step_*` where its shape allows, else the
plain `ssd_update`; a block through kernels/ssd.py's `ssd_block_*`).  An
"attention" layer keeps keys and values over the whole extent
("k_cache_<i>", "v_cache_<i>"
[batch, n_kv_head, max_len, d_head]) through `cached_attention`, grouped
heads read by index, no rotation, the model's own softmax scale.  Every
layer's feed-forward half is `decoder_block.share_feed_forward`: softmax
routing over the chosen experts (`moe(scoring="softmax",
norm_topk=True)`), the held range of the routed experts, and a shared
expert of **its own width** (`d_shared`).  The residual stream is
float32 whatever the weights' type (as `linear_moe_program.py`'s, and
for its reason).  The equations are in
`models/reference/granite_moe_hybrid.py`, which the tests hold this to.
"""

from .. import fluid
from ..fluid.param_attr import ParamAttr
from .decoder_block import (attention, block_positions, gated_feed_forward,
                            last, last_token_rows, linear, norm,
                            share_feed_forward)

__all__ = ["build_granite_hybrid_program", "granite_hybrid_param_names",
           "build_granite_hybrid_cached_step_program",
           "granite_moe_hybrid_param_names", "MAMBA", "ATTENTION",
           "STATE_IN_HEADS"]

MAMBA, ATTENTION = "mamba", "attention"
# of the state a served step was handed, the heads its "ssd_state_in"
# carries out: a tile of lanes at 64 a head.  Every head's update is the
# same arithmetic, and all 128 of two rows are 75 MB a step more to
# read and write, 1.1% of granite-decode-ep4's `decode_tok_per_s`
# (PERF.md section 6, PR 71)
STATE_IN_HEADS = 16

_MIXER_PARAMS = {
    "mamba": ("in_proj", "conv_w", "conv_b", "dt_bias", "a_log", "d",
              "norm_g", "out_proj"),
    "attention": ("wq", "wk", "wv", "wo"),
}
_SHARED_PARAMS = ("norm_1", "norm_2", "w_in", "w_out")
# a served share's: the shared expert beside the held routed experts
_SHARE_PARAMS = ("norm_1", "norm_2", "shared_in", "shared_out", "router",
                 "w_gate", "w_up", "w_down")


def granite_hybrid_param_names(layer_types):
    """The parameters' names, laid out as the reference's `params`."""
    return {
        "embed": "embed.w",
        "blocks": [{w: "block_%d.%s" % (i, w)
                    for w in _MIXER_PARAMS[kind] + _SHARED_PARAMS}
                   for i, kind in enumerate(layer_types)],
        "norm_f": "norm_f",
    }


def granite_moe_hybrid_param_names(layer_types):
    """The served share's parameters' names, laid out as
    `models/reference/granite_moe_hybrid.py`'s `params`."""
    return {
        "embed": "embed.w",
        "blocks": [{w: "block_%d.%s" % (i, w)
                    for w in _MIXER_PARAMS[kind] + _SHARE_PARAMS}
                   for i, kind in enumerate(layer_types)],
        "norm_f": "norm_f",
    }


def _mamba_mixer(h, names, n_heads, d_head, d_state, d_conv, chunk, eps,
                 carried=None):
    """The Mamba-2 mixer of h, already normed.  `carried` = (tail,
    state), a cached step's: the convolution and the scan start from
    them, the gated norm's ops are named (`ssd_gated_norm`: the
    instances in a trace start with it), and the result is (the mixer's
    output, tail_out, state_out, what the scan read: [x | B | C] after
    the convolution, and dt before the softplus)."""
    inner = n_heads * d_head
    z, xbc, dt = fluid.layers.split(
        linear(h, 2 * inner + 2 * d_state + n_heads, names["in_proj"]),
        [inner, inner + 2 * d_state, n_heads], dim=-1)
    tail, state = carried or (None, None)
    named = {"name": "ssd_gated_norm"} if carried else {}
    xbc = fluid.layers.causal_conv1d(
        xbc, filter_size=d_conv, activation="silu",
        param_attr=ParamAttr(name=names["conv_w"]),
        bias_attr=ParamAttr(name=names["conv_b"]), tail=tail)
    if carried:
        xbc, tail = xbc
    read = (xbc, dt)
    x, b, c = fluid.layers.split(xbc, [inner, d_state, d_state], dim=-1)
    y = fluid.layers.ssd_scan(
        x, dt, b, c, n_heads, chunk_size=chunk,
        a_log_attr=ParamAttr(name=names["a_log"]),
        d_attr=ParamAttr(name=names["d"]),
        dt_bias_attr=ParamAttr(name=names["dt_bias"]), state=state)
    if carried:
        y, state = y
        y = fluid.layers.rms_norm(
            fluid.layers.elementwise_mul(
                y, fluid.layers.swish(z, **named), **named),
            epsilon=eps, param_attr=ParamAttr(name=names["norm_g"]), **named)
    else:
        y = norm(y * fluid.layers.swish(z), eps, names["norm_g"])
    out = linear(y, h.shape[-1], names["out_proj"])
    return (out, tail, state, read) if carried else out


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


def build_granite_hybrid_cached_step_program(
        batch, max_len, vocab_size, layer_types=(MAMBA, ATTENTION),
        d_model=64, n_head=4, n_kv_head=2, d_head=None, mamba_heads=4,
        mamba_d_head=32, d_state=16, d_conv=4, chunk=8, d_expert=32,
        d_shared=None, n_experts=8, held=None, top_k=2, eps=1e-5,
        sm_scale=None, embedding_multiplier=1.0, residual_multiplier=1.0,
        logits_scaling=1.0, state_rows=0):
    """Returns (main, startup, logits, state_pairs, parts): feeds "tok"
    int32 [batch, T] (declared [batch, -1]: T = 1, or whole chunks of
    the scan, consecutive tokens of every row, read off the feed), "pos"
    int64 [batch], the position of the block's first token (rows move in
    lockstep), and the states the module's docstring names, a mamba
    layer's two and an attention layer's two (declared float32; a feed
    is taken in the type it arrives in); `logits` [batch, vocab_size],
    of the block's last position alone, over the tied table's
    `vocab_size` rows; `state_pairs` wires every state and the position,
    advanced by T, into `fluid.ProgramDecoder` (pass
    max_positions=max_len), which prefills a prompt `chunk` positions an
    application.

    `held` = (first, count) of the `n_experts` the router scores;
    `d_shared` the shared expert's width (default `d_expert`).

    `parts` are **of the block's last position**, in shapes that T does
    not change, as the linear builder's: per layer "hidden", "attn_in"
    and "attn_out" (the mixer's normed input and its output), the
    router's "top_w" and "top_idx" [batch, top_k], "moe_in" and the held
    experts' part "moe_out" [batch, 1, d_model], and "counts"; and with
    `state_rows` > 0, per mamba layer "ssd_state", the first
    `state_rows` rows of the state the step hands on (what a caller can
    afford to read back of 4 MB a row and layer), a head at a time as
    the recurrence has it: [state_rows, heads, d_head, d_state];
    "ssd_state_in", the same rows of the state it was handed, the first
    `STATE_IN_HEADS` heads of them, and "ssd_step_in" [state_rows, 1,
    heads * d_head + 2 * d_state + heads], what the scan read at the
    last position: [x | B | C | dt].  At T = 1 one update of
    "ssd_state_in" with "ssd_step_in" is those heads of "ssd_state": a
    caller can hold every layer's step to the recurrence by itself,
    whatever the layers before it did to its input."""
    if set(layer_types) - {MAMBA, ATTENTION}:
        raise ValueError("layer types %s are not %s / %s"
                         % (layer_types, MAMBA, ATTENTION))
    d_head = d_head or d_model // n_head
    names = granite_moe_hybrid_param_names(layer_types)
    inner = mamba_heads * mamba_d_head
    main = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(main, startup):
        def feed(name, shape, dtype="float32"):
            return fluid.layers.data(name=name, shape=shape, dtype=dtype,
                                     append_batch_size=False)

        tok = feed("tok", [batch, -1], "int32")
        pos = feed("pos", [batch], "int64")
        states = [
            [feed("conv_tail_%d" % i, [batch, d_conv - 1,
                                       inner + 2 * d_state]),
             feed("ssd_state_%d" % i, [batch, d_state, inner])]
            if kind == MAMBA else
            [feed("%s_cache_%d" % (which, i),
                  [batch, n_kv_head, max_len, d_head]) for which in "kv"]
            for i, kind in enumerate(layer_types)]
        embedded = fluid.layers.embedding(
            fluid.layers.reshape(x=fluid.layers.cast(tok, "int64"),
                                 shape=[0, 0, 1]),
            size=[vocab_size, d_model],
            param_attr=ParamAttr(name=names["embed"]))
        x = fluid.layers.scale(fluid.layers.cast(embedded, "float32"),
                               scale=float(embedding_multiplier))

        def normed(t, name):
            """RMSNorm of the float32 stream, in the weights' type."""
            return fluid.layers.cast(norm(t, eps, name), embedded)

        def entering(t):
            """What a sub-layer adds to the float32 stream."""
            return fluid.layers.scale(fluid.layers.cast(t, "float32"),
                                      scale=float(residual_multiplier))

        ones, _ = block_positions(tok, pos, batch)
        last_row = last_token_rows(ones, batch)
        state_pairs = []
        parts = {"hidden": [], "attn_in": [], "attn_out": [], "top_w": [],
                 "top_idx": [], "counts": [], "moe_in": [], "moe_out": [],
                 "ssd_state": [], "ssd_state_in": [], "ssd_step_in": []}

        def first_rows(t):
            return fluid.layers.slice(t, axes=[0], starts=[0],
                                      ends=[state_rows])

        def heads_apart(state, heads=mamba_heads):
            """The first rows' state a head at a time, as the recurrence
            has it: the first `heads` heads.  The rows are an array of
            their own (`own_layout`): turned as they are cut, where the
            compiler would else turn the whole carried state every step
            for their sake, 268 MB a layer beside a step kernel that
            takes the state as it lies (PERF.md section 6, PR 72)."""
            return fluid.layers.transpose(
                fluid.layers.reshape(
                    fluid.layers.slice(
                        state, axes=[0, 2], starts=[0, 0],
                        ends=[state_rows, heads * mamba_d_head],
                        own_layout=True),
                    [state_rows, d_state, heads, mamba_d_head]),
                [0, 2, 3, 1])

        def mamba(i, h, block):
            o, tail_out, state_out, read = _mamba_mixer(
                h, block, mamba_heads, mamba_d_head, d_state, d_conv,
                chunk, eps, carried=states[i])
            state_pairs.append(("conv_tail_%d" % i, tail_out.name))
            state_pairs.append(("ssd_state_%d" % i, state_out.name))
            if state_rows:
                parts["ssd_state"].append(heads_apart(state_out))
                parts["ssd_state_in"].append(heads_apart(
                    states[i][1], min(mamba_heads, STATE_IN_HEADS)))
                parts["ssd_step_in"].append(fluid.layers.concat(
                    [first_rows(last(t)) for t in read], axis=2))
            return o

        def attended(i, h, block):
            q = linear(h, n_head * d_head, block["wq"])
            k, v = (linear(h, n_kv_head * d_head, block[w])
                    for w in ("wk", "wv"))
            o, k_out, v_out = fluid.layers.cached_attention(
                q, k, v, states[i][0], states[i][1], pos, num_heads=n_head,
                num_kv_heads=n_kv_head, sm_scale=sm_scale)
            state_pairs.append(("k_cache_%d" % i, k_out.name))
            state_pairs.append(("v_cache_%d" % i, v_out.name))
            return linear(o, d_model, block["wo"])

        for i, block in enumerate(names["blocks"]):
            h = normed(x, block["norm_1"])
            parts["attn_in"].append(last(h))
            o = (mamba if layer_types[i] == MAMBA else attended)(i, h, block)
            parts["attn_out"].append(last(o))
            a = x + entering(o)
            f, routing = share_feed_forward(
                normed(a, block["norm_2"]), block, False, 0, d_expert,
                n_experts, held, top_k, True, 1.0, scoring="softmax",
                d_shared=d_shared)
            for key, value in routing.items():
                if key != "counts":     # the whole block's, as it comes
                    value = (last_row if key in ("top_w", "top_idx")
                             else last)(value)
                parts[key].append(value)
            x = a + entering(f)
            parts["hidden"].append(last(x))

        # the head reads the block's last position alone, through the
        # tied table's held rows
        table = main.global_block().var(names["embed"])
        logits = fluid.layers.reshape(
            x=fluid.layers.scale(
                fluid.layers.matmul(
                    normed(last(x), names["norm_f"]),
                    fluid.layers.transpose(table, [1, 0])),
                scale=1.0 / float(logits_scaling)),
            shape=[batch, vocab_size])
        pos_out = pos + fluid.layers.reduce_sum(ones)
        state_pairs.append(("pos", pos_out.name))
    return main, startup, logits, state_pairs, parts
