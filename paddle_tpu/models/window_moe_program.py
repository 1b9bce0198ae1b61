"""One chip's share of a decoder that mixes window and full attention
layers over grouped key/value heads, with sigmoid-routed experts, as a
cached decode step Program: K-EXAONE-236B-A23B's block
(huggingface.co/LGAI-EXAONE/K-EXAONE-236B-A23B, `model_type`
`exaone_moe`).

A block of T >= 1 consecutive tokens of every row in (T = 1: a decode
step; a prompt's prefill feeds many), the logits after the block's last
out, two caches a layer through the `cached_attention` op
(ops/attention.py), of two shapes in one step:
a `sliding_attention` layer keeps a ring of `window` slots a key/value
head ("k_cache_<i>", "v_cache_<i>" [batch, n_kv_head, window, d_head]:
whatever the session's length, a row's window layers hold their last
`window` positions), a `full_attention` layer the whole extent ([batch,
n_kv_head, max_len, d_head]).  Queries are `n_head` heads that read
`n_kv_head` key/value heads by index; q and k are RMS-normed head by
head over their `d_head` values (one learned [d_head] scale each a
layer); rotary positions (rotate-half, `rope_theta`) turn q and k on the
window layers alone, a full layer has none.  The block is pre-norm, two
norms a layer, and the residual stream is float32 whatever the weights'
type (a sub-layer reads it normed and cast to the weights' type, and
its output is cast up for the add: weights, caches and products stay in
the scope's type).  The feed-forward half is the latent builder's
(`decoder_block.share_feed_forward`: dense where `mlp_layer_types` says
so, else a shared expert beside the held range of the routed experts).
`fluid.ProgramDecoder` scans the step; the token feed is declared
[batch, -1], which is how a step says it takes a block
(`models/transformer_program.py`'s cached step settled it), so a prompt
is prefilled `models.decode.PREFILL_BLOCK` positions an application: a
block goes through a ring and through a whole-extent cache alike.

The equations are in `models/reference/exaone_moe.py`, which the tests
hold this to.
"""

from .. import fluid
from ..fluid.param_attr import ParamAttr
from .decoder_block import (block_positions, last, linear, norm,
                            share_feed_forward)

__all__ = ["build_window_moe_cached_step_program", "window_moe_param_names",
           "WINDOW", "FULL"]

WINDOW, FULL = "sliding_attention", "full_attention"
_ATTENTION = ("input_norm", "wq", "wk", "wv", "q_norm", "k_norm", "wo",
              "pre_mlp_norm")
_DENSE = ("ffn_in", "ffn_out")
_EXPERTS = ("shared_in", "shared_out", "router", "router_bias", "w_gate",
            "w_up", "w_down")


def window_moe_param_names(mlp_layer_types):
    """The parameters' names, laid out as the reference's `params`."""
    def block(i, kind):
        kinds = _ATTENTION + (_DENSE if kind == "dense" else _EXPERTS)
        return {w: "block_%d.%s" % (i, w) for w in kinds}

    return {"embed": "embed.w",
            "blocks": [block(i, kind)
                       for i, kind in enumerate(mlp_layer_types)],
            "norm_f": "norm_f", "head": "head.w"}


def build_window_moe_cached_step_program(
        batch, max_len, vocab_size, layer_types=(WINDOW, FULL),
        mlp_layer_types=("dense", "sparse"), window=4, n_head=4,
        n_kv_head=2, d_head=16, d_model=64, d_ff=128, d_expert=32,
        n_experts=8, held=None, top_k=2, norm_topk=True, routed_scale=2.5,
        eps=1e-5, rope_theta=1e6, n_group=0, topk_group=0):
    """Returns (main, startup, logits, state_pairs, parts): feeds "tok"
    int32 [batch, T] (declared [batch, -1]: T >= 1 consecutive tokens of
    every row, read off the feed), "pos" int64 [batch], the position of
    the block's first token (rows move in lockstep), and, a layer,
    "k_cache_<i>" and "v_cache_<i>" [batch, n_kv_head, `window` or
    `max_len`, d_head] (declared float32; a feed is taken in the type it
    arrives in, and the op casts a new entry to the cache's); `logits`
    [batch, vocab_size], of the block's last position alone;
    `state_pairs` wires the caches and the position, advanced by T,
    into `fluid.ProgramDecoder` (pass max_positions=max_len: the extent
    of the positions, which a window layer's ring does not hold).

    `parts` are **of the block's last position**, in shapes that T does
    not change (a decoder carries them through its scans as state pairs,
    and a carry keeps its shape): per layer "hidden", the layer's output
    [batch, 1, d_model] (the residual stream: float32), "attn_in", its attention sub-layer's normed
    input, and "attn_out", that sub-layer's output (after `wo`); per
    expert layer the router's "top_w" and "top_idx" [batch, top_k], the
    routed layer's input "moe_in" and its held experts' part "moe_out"
    [batch, 1, d_model]; and "counts", the experts' rows over the whole
    block (the expert op's own).  At T = 1 the slices are the
    identity."""
    if len(layer_types) != len(mlp_layer_types) \
            or set(layer_types) - {WINDOW, FULL} \
            or set(mlp_layer_types) - {"dense", "sparse"}:
        raise ValueError(
            "window_moe: layer_types %s and mlp_layer_types %s are not a "
            "%s / %s and a dense / sparse a layer"
            % (layer_types, mlp_layer_types, WINDOW, FULL))
    names = window_moe_param_names(mlp_layer_types)
    main = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(main, startup):
        tok = fluid.layers.data(name="tok", shape=[batch, -1],
                                dtype="int32", append_batch_size=False)
        pos = fluid.layers.data(name="pos", shape=[batch], dtype="int64",
                                append_batch_size=False)
        caches = [[fluid.layers.data(
            name="%s_cache_%d" % (which, i),
            shape=[batch, n_kv_head, window if kind == WINDOW else max_len,
                   d_head],
            dtype="float32", append_batch_size=False) for which in "kv"]
            for i, kind in enumerate(layer_types)]
        # lookup_table squeezes a trailing size-1 ids dim: [batch, T, 1]
        # ids give [batch, T, d_model]; 0 keeps an axis as it comes
        embedded = fluid.layers.embedding(
            fluid.layers.reshape(x=fluid.layers.cast(tok, "int64"),
                                 shape=[0, 0, 1]),
            size=[vocab_size, d_model],
            param_attr=ParamAttr(name=names["embed"]))
        # the residual stream is float32 whatever the weights' type:
        # every layer's input is built on it, and rounded to a scope's
        # bfloat16 after each of a layer's two adds it carries those
        # roundings, the largest a layer makes (the stream outgrows what
        # is added to it), into every product above.  A prompt prefilled
        # in blocks showed it: the cell's `correct` read a full layer's
        # attention 0.029 and 0.039 off where steps alone read 0.017
        # (PERF.md section 6, PR 46); with the stream float32 both forms
        # read 0.010-0.016
        x = fluid.layers.cast(embedded, "float32")

        def normed(t, name):
            """RMSNorm of the float32 stream, in the weights' type."""
            return fluid.layers.cast(norm(t, eps, name), embedded)
        # T is read off the token feed; positions [batch, T] are pos ..
        # pos + T - 1
        ones, positions = block_positions(tok, pos, batch)

        def head_norm(t, heads, name):
            """RMSNorm over each head's `d_head` values."""
            t = norm(fluid.layers.reshape(t, [0, 0, heads, d_head]), eps,
                     name)
            return fluid.layers.reshape(t, [0, 0, heads * d_head])

        def last_row(t):
            """[batch * T, top_k], a token a row -> [batch, top_k]."""
            return fluid.layers.reshape(
                last(fluid.layers.reshape(t, [batch, -1, top_k])),
                [batch, top_k])

        state_pairs = []
        parts = {"hidden": [], "attn_in": [], "attn_out": [], "top_w": [],
                 "top_idx": [], "counts": [], "moe_in": [], "moe_out": []}
        for i, block in enumerate(names["blocks"]):
            ring = layer_types[i] == WINDOW
            h = normed(x, block["input_norm"])
            parts["attn_in"].append(last(h))
            q = head_norm(linear(h, n_head * d_head, block["wq"]), n_head,
                          block["q_norm"])
            k = head_norm(linear(h, n_kv_head * d_head, block["wk"]),
                          n_kv_head, block["k_norm"])
            v = linear(h, n_kv_head * d_head, block["wv"])
            if ring:
                q = fluid.layers.rope(q, positions, n_head, rope_theta)
                k = fluid.layers.rope(k, positions, n_kv_head, rope_theta)
            o, k_out, v_out = fluid.layers.cached_attention(
                q, k, v, caches[i][0], caches[i][1], pos, num_heads=n_head,
                num_kv_heads=n_kv_head, window=window if ring else 0)
            state_pairs.append(("k_cache_%d" % i, k_out.name))
            state_pairs.append(("v_cache_%d" % i, v_out.name))
            o = linear(o, d_model, block["wo"])
            parts["attn_out"].append(last(o))
            a = x + fluid.layers.cast(o, "float32")
            u = normed(a, block["pre_mlp_norm"])
            f, routing = share_feed_forward(
                u, block, mlp_layer_types[i] == "dense", d_ff, d_expert,
                n_experts, held, top_k, norm_topk, routed_scale,
                router_bias=True, n_group=n_group, topk_group=topk_group)
            for key, value in (routing or {}).items():
                if key != "counts":     # the whole block's, as it comes
                    value = (last_row if key in ("top_w", "top_idx")
                             else last)(value)
                parts[key].append(value)
            x = a + fluid.layers.cast(f, "float32")
            parts["hidden"].append(last(x))

        # the head reads the block's last position alone
        logits = fluid.layers.reshape(
            x=linear(normed(last(x), names["norm_f"]), vocab_size,
                     names["head"]),
            shape=[batch, vocab_size])
        pos_out = pos + fluid.layers.reduce_sum(ones)
        state_pairs.append(("pos", pos_out.name))
    return main, startup, logits, state_pairs, parts
