"""One chip's share of a decoder whose every layer attends a *chosen*
set of its grouped key/value cache, with three-part rotary positions and
softmax-routed experts without a shared one, as a cached decode step
Program: the language model of Keye-VL-2.0-30B-A3B
(huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B, `model_type` `KeyeVL2`).

A block of T >= 1 consecutive tokens of every row in (T = 1: a decode
step; a prompt's prefill feeds many), the logits after the block's last
out, three caches a layer: keys and values of the whole extent
("k_cache_<i>", "v_cache_<i>" [batch, n_kv_head, max_len, d_head])
through `cached_attention`, and the chooser's keys ("index_cache_<i>"
[batch, max_len, its width]) through `mla_index_select`, DeepSeek-V3.2's
chooser as it is (`models/latent_moe_program.py` is its other user),
which hands the attention `Selected` and `Live`: a position reads
`top_k` slots of both caches, one set for every key/value head, whatever
the session's length.  q and k are RMS-normed head by head and turned by
three-part positions (`rope`'s `sections`); the chooser's queries and
keys by the temporal component alone.  A token's *slot* is `pos + t`,
its rotary position `pos + t + rope_delta`: after an image of h x w
tokens the position has advanced by max(h, w), not h * w, and a row
carries the difference as a state the step hands on unchanged.  The
residual stream is float32, the block pre-norm, as
`models/window_moe_program.py` has them (read there why).

The token feed is declared [batch, -1], which is how a step says it
takes a block (`fluid.ProgramDecoder` then prefills a prompt in blocks):
`mla_index_select` chooses a set for each of the block's positions and
`cached_attention` attends each position's own, a tile of positions at a
time, so what a block shares is the weights and the chooser's keys, read
once an application, and what it does not is the gathers, a position's
`top_k` slots of both caches each.  The block a prompt is prefilled by
is the step's own to say (`prefill_block`, the attention op's attr): the
token rows at which the dense products stop being bound by the weights'
read, as the latent builder's with a chooser.

This is a builder of its own and not a third layer kind of
`window_moe_program`: three caches a layer, three-part positions and the
chooser's ops between the projections and the attention; every line of
the window builder that wires a layer would have forked.  What the two
share is `decoder_block`.

With `images` the step also takes what a vision tower would hand it for
the block's positions: "mrope_pos" int64 [3, batch, T] (the positions'
three components, in place of `pos + t + rope_delta`), "image_embeds"
[batch, T, d_model] and "image_mask" float32 [batch, T, 1] (1 where the
vector takes the embedding's place).  `fluid.ProgramDecoder` feeds
tokens and state alone, so such a step is run by an executor or a
`FunctionalProgram` (a prefill pool's work; the tower itself is not in
the repository).

The equations are in `models/reference/keye_vl2.py`, which the tests
hold this to.
"""

from .. import fluid
from ..fluid.param_attr import ParamAttr
from .decoder_block import (block_positions, head_norm, last,
                            last_token_rows, linear, norm,
                            share_feed_forward)
from .latent_moe_program import sized_block

__all__ = ["build_sparse_kv_moe_cached_step_program",
           "sparse_kv_moe_param_names", "prefill_block"]

_BLOCK = ("input_norm", "wq", "wk", "wv", "q_norm", "k_norm", "wo",
          "w_iq", "w_ik", "ik_norm", "ik_norm_b", "w_iw", "pre_mlp_norm",
          "router", "w_gate", "w_up", "w_down")


def sparse_kv_moe_param_names(n_layer):
    """The parameters' names, laid out as the reference's `params`."""
    return {"embed": "embed.w",
            "blocks": [{w: "block_%d.%s" % (i, w) for w in _BLOCK}
                       for i in range(n_layer)],
            "norm_f": "norm_f", "head": "head.w"}


def prefill_block(batch, n_head, n_kv_head, d_head, indexer, max_len):
    """The positions of a row one application of the step prefills
    (`latent_moe_program.sized_block`, a chooser's): a position holds its
    heads' queries, keys and values in float32 beside its index scores
    and its set, and the block stops at the chooser's 512 token rows: 8
    rows take 64 positions."""
    return sized_block(batch, batch * (n_head + 2 * n_kv_head) * d_head * 4,
                       indexer, max_len)


def build_sparse_kv_moe_cached_step_program(
        batch, max_len, vocab_size, n_layer=2, n_head=4, n_kv_head=2,
        d_head=16, d_model=64, d_expert=32, n_experts=8, held=None, top_k=2,
        norm_topk=True, eps=1e-6, rope_theta=1e7, sections=(2, 3, 3),
        indexer=(4, 8, 4), images=False):
    """Returns (main, startup, logits, state_pairs, parts): feeds "tok"
    int32 [batch, T] (declared [batch, -1]: T >= 1 consecutive tokens of
    every row, read off the feed), "pos" int64 [batch], the slot the
    block's first token writes (rows move in lockstep), "rope_delta"
    int64 [batch], what a row's rotary position differs from its slot
    by, and, a layer, "k_cache_<i>", "v_cache_<i>" [batch, n_kv_head,
    max_len, d_head] and "index_cache_<i>" [batch, max_len, indexer[1]]
    (declared float32; a feed is taken in the type it arrives in, and
    the ops cast a new entry to the cache's); with `images` the three
    feeds the module's docstring names.  `indexer` = (heads, width,
    top_k) of the chooser; `sections` the pairs of a head each position
    component turns.  `logits` [batch, vocab_size], of the block's last
    position alone; `state_pairs` wires the caches, the position
    advanced by T and `rope_delta` as it came into `fluid.ProgramDecoder`
    (pass max_positions=max_len), which prefills a prompt
    `prefill_block(batch, n_head, n_kv_head, d_head, indexer, max_len)`
    positions an application (the attention op carries the number as an
    attr).

    `parts` are **of the block's last position**, in shapes that T does
    not change (a decoder carries them through its scans as state pairs,
    and a carry keeps its shape; at T = 1 the slices are the identity):
    per layer "hidden" the layer's output [batch, 1, d_model] (the
    residual stream: float32), "attn_in" its attention sub-layer's
    normed input, "attn_out" that sub-layer's output (after `wo`),
    "selected" [batch, indexer top_k] and "live" [batch] the chooser's
    two, the router's "top_w" and "top_idx" [batch, top_k], the routed
    layer's input "moe_in" and its held experts' part "moe_out" [batch,
    1, d_model], and the experts' "counts", their rows over the whole
    block (the expert op's own)."""
    if 2 * sum(sections) != d_head or len(sections) != 3:
        raise ValueError("sparse_kv_moe: sections %s do not add up to the "
                         "%d pairs of a head" % (list(sections),
                                                 d_head // 2))
    i_heads, i_dim, i_top_k = indexer
    names = sparse_kv_moe_param_names(n_layer)
    main = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(main, startup):
        def feed(name, shape, dtype):
            return fluid.layers.data(name=name, shape=shape, dtype=dtype,
                                     append_batch_size=False)

        tok = feed("tok", [batch, -1], "int32")
        pos = feed("pos", [batch], "int64")
        delta = feed("rope_delta", [batch], "int64")
        caches = [[feed("%s_cache_%d" % (which, i),
                        [batch, n_kv_head, max_len, d_head], "float32")
                   for which in "kv"] for i in range(n_layer)]
        index_caches = [feed("index_cache_%d" % i, [batch, max_len, i_dim],
                             "float32") for i in range(n_layer)]
        # lookup_table squeezes a trailing size-1 ids dim: [batch, T, 1]
        # ids give [batch, T, d_model]; 0 keeps an axis as it comes
        embedded = fluid.layers.embedding(
            fluid.layers.reshape(x=fluid.layers.cast(tok, "int64"),
                                 shape=[0, 0, 1]),
            size=[vocab_size, d_model],
            param_attr=ParamAttr(name=names["embed"]))
        x = fluid.layers.cast(embedded, "float32")
        # T is read off the token feed; slots [batch, T] are pos .. pos +
        # T - 1.  What a decoder is handed of the step is of the block's
        # last position
        ones, slots = block_positions(tok, pos, batch)
        final, final_row = last, last_token_rows(ones, batch)
        if images:
            three = feed("mrope_pos", [3, batch, -1], "int64")
            vectors = feed("image_embeds", [batch, -1, d_model], "float32")
            mask = feed("image_mask", [batch, -1, 1], "float32")
            x = x + mask * (fluid.layers.cast(vectors, "float32") - x)
        else:
            # a text token: one position, three times
            text = fluid.layers.reshape(
                x=slots + fluid.layers.reshape(x=delta, shape=[batch, 1]),
                shape=[1, batch, -1])
            three = fluid.layers.concat([text, text, text], axis=0)
        temporal = fluid.layers.reshape(
            fluid.layers.slice(three, axes=[0], starts=[0], ends=[1]),
            [batch, -1])
        sized = prefill_block(batch, n_head, n_kv_head, d_head, indexer,
                              max_len)

        def normed(t, name):
            """RMSNorm of the float32 stream, in the weights' type."""
            return fluid.layers.cast(norm(t, eps, name), embedded)

        def turn(t, heads):
            return fluid.layers.rope(t, three, heads, rope_theta,
                                     sections=sections)

        state_pairs = []
        parts = {key: [] for key in (
            "hidden", "attn_in", "attn_out", "selected", "live", "top_w",
            "top_idx", "counts", "moe_in", "moe_out")}
        for i, block in enumerate(names["blocks"]):
            h = normed(x, block["input_norm"])
            parts["attn_in"].append(final(h))
            q = turn(head_norm(linear(h, n_head * d_head, block["wq"]),
                               n_head, d_head, eps, block["q_norm"]),
                     n_head)
            k = turn(head_norm(linear(h, n_kv_head * d_head, block["wk"]),
                               n_kv_head, d_head, eps, block["k_norm"]),
                     n_kv_head)
            v = linear(h, n_kv_head * d_head, block["wv"])
            k_index = fluid.layers.layer_norm(
                linear(h, i_dim, block["w_ik"]), begin_norm_axis=2,
                epsilon=eps, param_attr=ParamAttr(name=block["ik_norm"]),
                bias_attr=ParamAttr(name=block["ik_norm_b"]))
            selected, live, index_out = fluid.layers.mla_index_select(
                fluid.layers.rope(linear(h, i_heads * i_dim, block["w_iq"]),
                                  temporal, i_heads, rope_theta),
                linear(h, i_heads, block["w_iw"]),
                fluid.layers.rope(k_index, temporal, 1, rope_theta),
                index_caches[i], pos, i_heads, i_top_k,
                scale=(i_heads * i_dim) ** -0.5)
            # a set a position of the block, a position after a position
            # a row: the last is a token row's
            parts["selected"].append(final_row(
                fluid.layers.reshape(selected, [-1, i_top_k])))
            parts["live"].append(fluid.layers.reshape(
                final_row(fluid.layers.reshape(live, [-1, 1])), [batch]))
            o, k_out, v_out = fluid.layers.cached_attention(
                q, k, v, caches[i][0], caches[i][1], pos, num_heads=n_head,
                num_kv_heads=n_kv_head, selected=selected, live=live,
                prefill_block=sized)
            state_pairs += [("k_cache_%d" % i, k_out.name),
                            ("v_cache_%d" % i, v_out.name),
                            ("index_cache_%d" % i, index_out.name)]
            o = linear(o, d_model, block["wo"])
            parts["attn_out"].append(final(o))
            a = x + fluid.layers.cast(o, "float32")
            u = normed(a, block["pre_mlp_norm"])
            f, routing = share_feed_forward(
                u, block, False, 0, d_expert, n_experts, held, top_k,
                norm_topk, 1.0, scoring="softmax")
            for key, value in routing.items():
                if key != "counts":     # the whole block's, as it comes
                    value = (final_row if key in ("top_w", "top_idx")
                             else final)(value)
                parts[key].append(value)
            x = a + fluid.layers.cast(f, "float32")
            parts["hidden"].append(final(x))

        # the head reads the block's last position alone
        logits = fluid.layers.reshape(
            x=linear(normed(final(x), names["norm_f"]), vocab_size,
                     names["head"]),
            shape=[batch, vocab_size])
        pos_out = pos + fluid.layers.reduce_sum(ones)
        state_pairs.append(("pos", pos_out.name))
        # handed on as it came: a state the step reads and never changes
        state_pairs.append(("rope_delta",
                            fluid.layers.cast(delta, "int64").name))
    return main, startup, logits, state_pairs, parts
