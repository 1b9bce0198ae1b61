"""One chip's share of a latent-attention mixture-of-experts decoder as a
cached decode step Program: the DeepSeek-V3 family's block
(arXiv:2412.19437), as openPangu-Ultra-MoE-718B
(huggingface.co/FreedomIntelligence/openPangu-Ultra-MoE-718B) has it
with sandwich norms, and as DeepSeek-V3.2
(huggingface.co/deepseek-ai/DeepSeek-V3.2) has it pre-norm with a learned
chooser of the cache slots its attention reads, and as Hy4-preview
(huggingface.co/tencent/Hy4-preview) has it with a chooser on some
layers only, a residual of several streams, a gate on the attention's
output, a sink in its softmax and a clamp in its feed-forwards.

A block of T >= 1 consecutive tokens of every row in (T = 1: a decode
step; a prompt's prefill feeds many), the logits after the block's last
out, one cache of latents a layer
through the `mla_cached_attention` op (ops/attention.py): `c` and the
rotated shared key `r` side by side, `kv_rank + d_rope` values a token,
and no head's key or value.  The feed-forward is dense in the first
`n_dense` layers and after them a shared expert beside a routed layer
(`fluid.layers.moe`: sigmoid scores, the chosen weights normalised and
scaled) that holds the experts `held` = (first, count) of the
`n_experts` its router scores: what one chip of an expert-parallel
deployment computes, with no exchange and nothing that stands in for the
other chips.  `fluid.ProgramDecoder` scans the step.  The token feed is
declared [batch, -1], which is how a step says it takes a block
(`models/window_moe_program.py`), and the block a prompt is prefilled by
is the step's own to say (`prefill_block`): the absorbed queries of an
application, `n_head * (kv_rank + d_rope)` values a token, are what a
block of this step costs in memory, a hundred times its hidden state, so
the most positions an application takes follow from the step's rows and
widths and not from `models.decode.PREFILL_BLOCK`.  The block reads the
weights once for all its positions: a prompt's prefill is a few
compute-bound applications, not a weight-bound one a position (its
`rope` ops carry `full_width`, so a block's rotary heads of 64 are
turned where they lie and not through a view of half heads).  With an
`indexer` the step takes a block as well: `mla_index_select` chooses a
set for each of the block's positions and `mla_cached_attention` attends
each position's own, a tile of positions at a time, so what a block
shares is the weights and what it does not is the gathers, a position's
`top_k` rows each.  Such a block is sized by the rows at which the dense
products stop being bound by the weights' read (`prefill_block`).

What a model's options change:

- `sandwich_norm` (pangu: True): every sub-layer's output is normed
  before it is added, four norms a layer; False is the pre-norm block,
  two norms a layer (`input_norm`, `pre_mlp_norm`).
- `indexer` = (heads, width, top_k) (DeepSeek-V3.2's lightning indexer):
  a layer carries a second cache, `index_cache_<i>` [batch, max_len,
  width], of one small key a token.  The normed query latent goes to two
  consumers, the heads' up-projections and the index queries `w_iq`;
  the index key is LayerNorm(h `w_ik`), the first `d_rope` values of it
  and of every index query rotated; the index heads' weights are h
  `w_iw`; `mla_index_select` writes the block's keys, scores each
  position's live slots and picks its `top_k`, and
  `mla_cached_attention` attends those.
- `n_group`, `topk_group`, `router_bias`: the router's choice limited to
  the best groups of experts and steered by a selection bias
  (`fluid.layers.moe`).
- `yarn` = {"factor", "original_positions", "beta_fast", "beta_slow",
  "mscale"}: YaRN's blended rotary frequencies for every rotation, and
  the attention's scale times (0.1 mscale ln factor + 1)^2.

- `indexer_types`, a "full" or a "shared" a layer (Hy4-preview's): a
  "shared" layer holds no index weights and **no `index_cache_<i>`**,
  and its attention reads the `Selected` / `Live` of the nearest "full"
  layer below it ([batch, T, top_k] and [batch, T] of a block): a chosen
  set made in one layer is read by every layer up to the next that
  chooses.  The step's state is then one cache a
  layer and a second on the layers that choose.
- `hc` = {"streams", "eps", "magnitude", "iterations"}: the residual is
  `streams` streams [batch, T, streams, d_model], the embedding repeated,
  and every sub-layer reads its input off them and writes its output to
  them through mappings of its own (`ops/hyper_connection.py`: `hc_maps`,
  `hc_pre`, `hc_post`, float32 inside; two applications a layer); the
  streams are summed before the final norm.
- `gated`: the heads' outputs times sigmoid(h `w_g`), elementwise,
  before `wo` (ops named `mla_gate`).  `sink`: a learned logit a head in
  the softmax's denominator (`mla_cached_attention`'s `Sink`).
- `swiglu_limit` L: every gated feed-forward, dense, shared and routed,
  is silu(min(gate, L)) * clip(up, -L, L).
- `head_float32`: the final norm and the head in float32, the logits
  float32 (`decoder_block.linear_float32`).

The equations are in `models/reference/pangu_moe.py`,
`models/reference/deepseek_v32.py` and `models/reference/hy4_preview.py`,
which the tests hold this to.
"""

from .. import fluid
from ..fluid.param_attr import ParamAttr
from ..ops.attention import TILE_BYTES, yarn_inv_freq, yarn_mscale
from .decode import PREFILL_BLOCK
from ..obs import telemetry
from .decoder_block import (block_positions, last, last_token_rows, linear,
                            linear_float32, norm, share_feed_forward)

__all__ = ["build_latent_moe_cached_step_program", "latent_moe_param_names",
           "prefill_block", "sized_block"]

_ATTENTION = ("input_norm", "w_dq", "q_norm", "w_uq_nope", "w_uq_rope",
              "w_dkv", "kv_norm", "w_uk", "w_uv", "wo")
_INDEXER = ("w_iq", "w_ik", "ik_norm", "ik_norm_b", "w_iw")
_DENSE = ("ffn_in", "ffn_out")
_EXPERTS = ("shared_in", "shared_out", "router", "w_gate", "w_up", "w_down")
# a hyper-connection's projections, scalars and biases, for the attention
# sub-layer and for the feed-forward
_STREAMS = tuple("hc_%s_%s" % (sub, what) for sub in ("attn", "mlp")
                 for what in ("p", "a", "b"))


def latent_moe_param_names(n_layer, n_dense, sandwich_norm=True,
                           indexer=False, router_bias=False,
                           indexer_types=None, hc=False, gated=False,
                           sink=False):
    """The parameters' names, laid out as the reference's `params`; the
    indexer's (on the layers `indexer_types` calls "full", every layer
    where it is None), the router's bias, the sandwich's two further
    norms, the hyper-connections', the gate's and the sink's only where
    the options ask for them."""
    def block(i):
        chooses = indexer and (indexer_types is None
                               or indexer_types[i] == "full")
        kinds = _ATTENTION \
            + (("post_attn_norm",) if sandwich_norm else ()) \
            + ("pre_mlp_norm",) + (_INDEXER if chooses else ()) \
            + (("w_g",) if gated else ()) + (("sink",) if sink else ()) \
            + (_STREAMS if hc else ()) \
            + (_DENSE if i < n_dense else _EXPERTS
               + (("router_bias",) if router_bias else ())) \
            + (("post_mlp_norm",) if sandwich_norm else ())
        return {w: "block_%d.%s" % (i, w) for w in kinds}

    return {"embed": "embed.w", "blocks": [block(i) for i in range(n_layer)],
            "norm_f": "norm_f", "head": "head.w"}


# What the absorbed queries and the latent sums of one application of the
# step may hold, in a 16-bit type (what a share of this size is served
# in): [rows, T, heads, latent + rope] and [rows, T, heads, latent], the
# largest arrays a block makes.  pangu-decode-ep16 serves 13.5 of the
# chip's 16.9 GB; an application's other temporaries (the heads' queries,
# the dense feed-forward's 2 x 18432 columns, the expert op's rows) come
# to about as much again
_BLOCK_BYTES = 5 << 28


# The token rows of an application past which a chooser's block gains
# nothing: twice the 240 multiply-adds a byte of weights at which the
# v5e's dense products turn from bound by the weights' read to bound by
# arithmetic.  What such a block does not share, the gathers of its
# positions' chosen sets, costs the same a position however many ride
# together, and the step serves beside sessions that fill the chip
# (dsv32-turn-16k-ep16: 88% of it), so a longer block buys memory alone
_CHOOSER_ROWS = 512


def prefill_block(batch, n_head, kv_rank, d_rope, indexer=None, max_len=0):
    """The positions of a row one application of the step prefills: the
    largest power of two, `models.decode.PREFILL_BLOCK` at most, whose
    absorbed queries and latent sums over `batch` rows stay within
    `_BLOCK_BYTES`; a power of two so that it divides the prompts it is
    likely to see, and no remainder block is a second program.  256 rows
    of 128 heads over 512 + 64: 16 positions, 4096 tokens an
    application, which is compute-bound already (13.9 TFLOP, 71 ms at
    the v5e's peak, against 12 ms to read the weights).  With an
    `indexer`, what `sized_block` says of a chooser: 16 rows take 32
    positions, 8 rows 64."""
    return sized_block(batch, batch * n_head * (2 * kv_rank + d_rope) * 2,
                       indexer, max_len)


def sized_block(batch, a_position, indexer=None, max_len=0):
    """`prefill_block` for a step whose largest arrays hold `a_position`
    bytes a position of the block over its `batch` rows.  With an
    `indexer` (heads, width, top_k) over `max_len` slots (a chooser's
    step, this builder's or `models/sparse_kv_moe_program.py`'s) a
    position also holds its index scores [max_len] float32 and its set
    [top_k] int32, the budget is less the two tiles a chooser's block
    works through (`ops.attention.TILE_BYTES`: the index scores before
    the heads are summed, the gathered rows beside their attention's
    scores), and the block stops at `_CHOOSER_ROWS` token rows."""
    most, room = PREFILL_BLOCK, _BLOCK_BYTES
    if indexer is not None:
        a_position += batch * (max_len + indexer[2]) * 4
        most = min(most, max(1, _CHOOSER_ROWS // batch))
        room -= 2 * TILE_BYTES
    block = 1
    while block < most and 2 * block * a_position <= room:
        block *= 2
    return block


def build_latent_moe_cached_step_program(
        batch, max_len, vocab_size, n_layer=2, n_dense=1, n_head=4,
        d_model=64, q_rank=32, kv_rank=16, d_nope=16, d_rope=8, d_v=16,
        d_ff=128, d_expert=32, n_experts=8, held=None, top_k=2,
        norm_topk=True, routed_scale=2.5, eps=1e-5, rope_theta=1e4,
        sandwich_norm=True, indexer=None, n_group=0, topk_group=0,
        router_bias=False, yarn=None, indexer_types=None, hc=None,
        gated=False, sink=False, swiglu_limit=None, head_float32=False):
    """Returns (main, startup, logits, state_pairs, parts): feeds "tok"
    int32 [batch, T] (declared [batch, -1]: T >= 1 consecutive tokens of
    every row, read off the feed), "pos" int64 [batch], the position of
    the block's first token (rows move in lockstep), and
    "latent_cache_<i>" [batch,
    max_len, kv_rank + d_rope] a layer (declared float32; a feed is taken
    in the type it arrives in, and the op casts a new entry to the
    cache's); `logits` [batch, vocab_size], of the block's last position
    alone; `state_pairs` wires the caches and the position, advanced by
    T, into
    `fluid.ProgramDecoder` (pass max_positions=max_len), which prefills
    a prompt `prefill_block(batch, n_head, kv_rank, d_rope, indexer,
    max_len)` positions an application (the attention op carries the
    number as an attr).  With an `indexer` there is also
    "index_cache_<i>" [batch, max_len, its width] a layer that chooses
    (every layer, or those `indexer_types` calls "full").

    `parts` are **of the block's last position**, in shapes that T does
    not change (a decoder carries them through its scans as state pairs,
    and a carry keeps its shape; at T = 1 the slices are the identity):
    per expert layer the router's Variables "top_w" and "top_idx"
    [batch, top_k], the routed layer's input "moe_in" and its
    held experts' part "moe_out" [batch, 1, d_model], and "counts", the
    experts' rows over the whole block (the expert op's own); per layer
    "hidden", the layer's output [batch, 1, d_model], "attn_in", its
    attention sub-layer's normed input, and "attn_out", that sub-layer's
    output (after `wo`, before any norm); and with an
    `indexer`, per layer, "selected" [batch, top_k] and "live"
    [batch], the set the block's last position attends in the layer (a
    "shared" layer's are the Variables of the layer it inherits from).
    With `hc`, "hidden" is the
    streams after the layer [batch, 1, streams, d_model], and per layer
    "streams_in" and "streams_out" are the streams before the attention
    sub-layer and after it."""
    if indexer_types is not None:
        indexer_types = list(indexer_types)
        if indexer is None or len(indexer_types) != n_layer \
                or indexer_types[0] != "full" \
                or set(indexer_types) - {"full", "shared"}:
            raise ValueError(
                "indexer_types %r: a \"full\" or a \"shared\" for each of "
                "the %d layers of a step with an indexer, the first of "
                "them \"full\"" % (indexer_types, n_layer))
    names = latent_moe_param_names(
        n_layer, n_dense, sandwich_norm, indexer is not None, router_bias,
        indexer_types, hc is not None, gated, sink)
    width = kv_rank + d_rope
    inv_freq = sm_scale = None
    if yarn is not None:
        inv_freq = yarn_inv_freq(
            d_rope, rope_theta, yarn["factor"], yarn["original_positions"],
            yarn["beta_fast"], yarn["beta_slow"])
        sm_scale = (d_nope + d_rope) ** -0.5 \
            * yarn_mscale(yarn["factor"], yarn.get("mscale", 1.0)) ** 2

    def rotate(x, heads, rotary_dim=None):
        return fluid.layers.rope(x, positions, heads, rope_theta,
                                 inv_freq=inv_freq, rotary_dim=rotary_dim,
                                 full_width=True)

    main = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(main, startup):
        tok = fluid.layers.data(name="tok", shape=[batch, -1],
                                dtype="int32", append_batch_size=False)
        pos = fluid.layers.data(name="pos", shape=[batch], dtype="int64",
                                append_batch_size=False)
        caches = [fluid.layers.data(
            name="latent_cache_%d" % i, shape=[batch, max_len, width],
            dtype="float32", append_batch_size=False)
            for i in range(n_layer)]
        index_caches = {}
        if indexer is not None:
            i_heads, i_dim, i_top_k = indexer
            index_caches = {i: fluid.layers.data(
                name="index_cache_%d" % i, shape=[batch, max_len, i_dim],
                dtype="float32", append_batch_size=False)
                for i in range(n_layer)
                if indexer_types is None or indexer_types[i] == "full"}
        # lookup_table squeezes a trailing size-1 ids dim: [batch, T, 1]
        # ids give [batch, T, d_model]; 0 keeps an axis as it comes
        x = fluid.layers.embedding(
            fluid.layers.reshape(x=fluid.layers.cast(tok, "int64"),
                                 shape=[0, 0, 1]),
            size=[vocab_size, d_model],
            param_attr=ParamAttr(name=names["embed"]))
        # T is read off the token feed; positions [batch, T] are
        # pos .. pos + T - 1.  What a decoder is handed of the step
        # is of the block's last position
        ones, positions = block_positions(tok, pos, batch)
        final, final_row = last, last_token_rows(ones, batch)
        sized = {"prefill_block": prefill_block(
            batch, n_head, kv_rank, d_rope, indexer, max_len)}

        state_pairs = []
        parts = {"hidden": [], "attn_in": [], "attn_out": [], "top_w": [],
                 "top_idx": [],
                 "counts": [], "moe_in": [], "moe_out": [], "selected": [],
                 "live": []}
        streams = None
        if hc is not None:
            # the embedding repeated: [batch, T, d] -> [batch, T, n, d]
            streams = fluid.layers.expand(
                fluid.layers.reshape(x, [0, 0, 1, d_model]),
                [1, 1, hc["streams"], 1])
            parts.update(streams_in=[], streams_out=[])

        def read_streams(block, sub):
            """(a sub-layer's input read off the streams, the two
            mappings that write its output back)."""
            pre, post, res = fluid.layers.hc_maps(
                streams, *(ParamAttr(name=block["hc_%s_%s" % (sub, w)])
                           for w in ("p", "a", "b")),
                epsilon=hc["eps"], magnitude=hc["magnitude"],
                iterations=hc["iterations"])
            return fluid.layers.hc_pre(streams, pre), (res, post)

        chosen = {}
        for i, block in enumerate(names["blocks"]):
            if streams is not None:
                parts["streams_in"].append(final(streams))
                x, back = read_streams(block, "attn")
            h = norm(x, eps, block["input_norm"])
            parts["attn_in"].append(final(h))
            c_q = norm(linear(h, q_rank, block["w_dq"]), eps,
                       block["q_norm"])
            q_nope = linear(c_q, n_head * d_nope, block["w_uq_nope"])
            q_rope = rotate(linear(c_q, n_head * d_rope, block["w_uq_rope"]),
                            n_head)
            c, r = fluid.layers.split(
                linear(h, width, block["w_dkv"]), [kv_rank, d_rope], dim=-1)
            index_out = None
            if i in index_caches:
                k_index = fluid.layers.layer_norm(
                    linear(h, i_dim, block["w_ik"]), begin_norm_axis=2,
                    epsilon=eps, param_attr=ParamAttr(name=block["ik_norm"]),
                    bias_attr=ParamAttr(name=block["ik_norm_b"]))
                selected, live, index_out = fluid.layers.mla_index_select(
                    rotate(linear(c_q, i_heads * i_dim, block["w_iq"]),
                           i_heads, d_rope),
                    linear(h, i_heads, block["w_iw"]),
                    rotate(k_index, 1, d_rope), index_caches[i], pos,
                    i_heads, i_top_k, scale=(i_heads * i_dim) ** -0.5)
                chosen = {"selected": selected, "live": live}
                # a set a position of the block, a position after a
                # position a row: the last is a token row's
                of_last = {
                    "selected": final_row(
                        fluid.layers.reshape(selected, [-1, i_top_k])),
                    "live": fluid.layers.reshape(
                        final_row(fluid.layers.reshape(live, [-1, 1])),
                        [batch])}
            # a layer that does not choose attends the set of the nearest
            # layer below it that did (none: every slot)
            for what in chosen:
                parts[what].append(of_last[what])
            a_sink = {"sink_attr": ParamAttr(name=block["sink"])} \
                if sink else {}
            o, cache_out = fluid.layers.mla_cached_attention(
                q_nope, q_rope, norm(c, eps, block["kv_norm"]),
                rotate(r, 1), caches[i],
                pos, n_head, d_v, uk_attr=ParamAttr(name=block["w_uk"]),
                uv_attr=ParamAttr(name=block["w_uv"]), sm_scale=sm_scale,
                **chosen, **sized, **a_sink)
            state_pairs.append(("latent_cache_%d" % i, cache_out.name))
            if index_out is not None:
                state_pairs.append(("index_cache_%d" % i, index_out.name))
            if gated:
                # named: the ops' instances in a trace start with it
                o = fluid.layers.elementwise_mul(
                    o, fluid.layers.sigmoid(
                        fluid.layers.fc(
                            input=h, size=n_head * d_v, num_flatten_dims=2,
                            param_attr=ParamAttr(name=block["w_g"]),
                            bias_attr=False, name="mla_gate"),
                        name="mla_gate"), name="mla_gate")
            o = linear(o, d_model, block["wo"])
            parts["attn_out"].append(final(o))
            o = norm(o, eps, block["post_attn_norm"]) if sandwich_norm else o
            if streams is not None:
                streams = fluid.layers.hc_post(streams, *back, o)
                parts["streams_out"].append(final(streams))
                a, back = read_streams(block, "mlp")
            else:
                a = x + o
            u = norm(a, eps, block["pre_mlp_norm"])
            f, routing = share_feed_forward(
                u, block, i < n_dense, d_ff, d_expert, n_experts, held,
                top_k, norm_topk, routed_scale, router_bias, n_group,
                topk_group, swiglu_limit=swiglu_limit)
            for key, value in (routing or {}).items():
                if key != "counts":     # the whole block's, as it comes
                    value = (final_row if key in ("top_w", "top_idx")
                             else final)(value)
                parts[key].append(value)
            f = norm(f, eps, block["post_mlp_norm"]) if sandwich_norm else f
            if streams is not None:
                streams = fluid.layers.hc_post(streams, *back, f)
                parts["hidden"].append(final(streams))
            else:
                x = a + f
                parts["hidden"].append(final(x))

        # the head reads the block's last position alone
        x = final(x if streams is None else streams)
        if head_float32:
            x = fluid.layers.cast(x, "float32")
        if streams is not None:
            x = fluid.layers.reduce_sum(x, dim=2)
        logits = fluid.layers.reshape(
            x=(linear_float32 if head_float32 else linear)(
                norm(x, eps, names["norm_f"]), vocab_size, names["head"]),
            shape=[batch, vocab_size])
        pos_out = pos + fluid.layers.reduce_sum(ones)
        state_pairs.append(("pos", pos_out.name))
    if indexer_types is not None and "shared" in indexer_types:
        telemetry.on_index_sets_reused(main, indexer_types.count("shared"))
    return main, startup, logits, state_pairs, parts
