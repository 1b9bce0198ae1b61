"""One chip's share of a latent-attention mixture-of-experts decoder as a
cached decode step Program: the DeepSeek-V3 family's block
(arXiv:2412.19437), as openPangu-Ultra-MoE-718B
(huggingface.co/FreedomIntelligence/openPangu-Ultra-MoE-718B) has it
with sandwich norms, and as DeepSeek-V3.2
(huggingface.co/deepseek-ai/DeepSeek-V3.2) has it pre-norm with a learned
chooser of the cache slots its attention reads.

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
`indexer` the step takes one position a call, [batch], and prefill is
its scan over the prompt: a chosen set is one position's
(`mla_index_select` chooses for one query).

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
  `w_iw`; `mla_index_select` writes the key, scores the live slots and
  picks `top_k`, and `mla_cached_attention` attends those.
- `n_group`, `topk_group`, `router_bias`: the router's choice limited to
  the best groups of experts and steered by a selection bias
  (`fluid.layers.moe`).
- `yarn` = {"factor", "original_positions", "beta_fast", "beta_slow",
  "mscale"}: YaRN's blended rotary frequencies for every rotation, and
  the attention's scale times (0.1 mscale ln factor + 1)^2.

The equations are in `models/reference/pangu_moe.py` and
`models/reference/deepseek_v32.py`, which the tests hold this to.
"""

from .. import fluid
from ..fluid.param_attr import ParamAttr
from ..ops.attention import yarn_inv_freq, yarn_mscale
from .decode import PREFILL_BLOCK
from .decoder_block import (block_positions, last, last_token_rows, linear,
                            norm, share_feed_forward)

__all__ = ["build_latent_moe_cached_step_program", "latent_moe_param_names",
           "prefill_block"]

_ATTENTION = ("input_norm", "w_dq", "q_norm", "w_uq_nope", "w_uq_rope",
              "w_dkv", "kv_norm", "w_uk", "w_uv", "wo")
_INDEXER = ("w_iq", "w_ik", "ik_norm", "ik_norm_b", "w_iw")
_DENSE = ("ffn_in", "ffn_out")
_EXPERTS = ("shared_in", "shared_out", "router", "w_gate", "w_up", "w_down")


def latent_moe_param_names(n_layer, n_dense, sandwich_norm=True,
                           indexer=False, router_bias=False):
    """The parameters' names, laid out as the reference's `params`; the
    indexer's, the router's bias and the sandwich's two further norms
    only where the options ask for them."""
    def block(i):
        kinds = _ATTENTION \
            + (("post_attn_norm",) if sandwich_norm else ()) \
            + ("pre_mlp_norm",) + (_INDEXER if indexer else ()) \
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


def prefill_block(batch, n_head, kv_rank, d_rope):
    """The positions of a row one application of the step prefills: the
    largest power of two, `models.decode.PREFILL_BLOCK` at most, whose
    absorbed queries and latent sums over `batch` rows stay within
    `_BLOCK_BYTES`; a power of two so that it divides the prompts it is
    likely to see, and no remainder block is a second program.  256 rows
    of 128 heads over 512 + 64: 16 positions, 4096 tokens an
    application, which is compute-bound already (13.9 TFLOP, 71 ms at
    the v5e's peak, against 12 ms to read the weights)."""
    a_position = batch * n_head * (2 * kv_rank + d_rope) * 2
    block = 1
    while block < PREFILL_BLOCK and 2 * block * a_position <= _BLOCK_BYTES:
        block *= 2
    return block


def build_latent_moe_cached_step_program(
        batch, max_len, vocab_size, n_layer=2, n_dense=1, n_head=4,
        d_model=64, q_rank=32, kv_rank=16, d_nope=16, d_rope=8, d_v=16,
        d_ff=128, d_expert=32, n_experts=8, held=None, top_k=2,
        norm_topk=True, routed_scale=2.5, eps=1e-5, rope_theta=1e4,
        sandwich_norm=True, indexer=None, n_group=0, topk_group=0,
        router_bias=False, yarn=None):
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
    a prompt `prefill_block(batch, n_head, kv_rank, d_rope)` positions an
    application (the attention op carries the number as an attr).  With
    an `indexer` the step takes one position: "tok" is int32 [batch],
    there is also "index_cache_<i>" [batch, max_len, its width] a layer,
    and T below is 1.

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
    [batch]."""
    names = latent_moe_param_names(n_layer, n_dense, sandwich_norm,
                                   indexer is not None, router_bias)
    width = kv_rank + d_rope
    inv_freq = sm_scale = None
    if yarn is not None:
        inv_freq = yarn_inv_freq(
            d_rope, rope_theta, yarn["factor"], yarn["original_positions"],
            yarn["beta_fast"], yarn["beta_slow"])
        sm_scale = (d_nope + d_rope) ** -0.5 \
            * yarn_mscale(yarn["factor"], yarn.get("mscale", 1.0)) ** 2

    # the all-slots step takes a block of positions; a chooser's step one
    # position, and is built as it was
    takes_block = indexer is None

    def rotate(x, heads, rotary_dim=None):
        return fluid.layers.rope(x, positions, heads, rope_theta,
                                 inv_freq=inv_freq, rotary_dim=rotary_dim,
                                 full_width=takes_block)

    main = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(main, startup):
        tok = fluid.layers.data(
            name="tok", shape=[batch, -1] if takes_block else [batch],
            dtype="int32", append_batch_size=False)
        pos = fluid.layers.data(name="pos", shape=[batch], dtype="int64",
                                append_batch_size=False)
        caches = [fluid.layers.data(
            name="latent_cache_%d" % i, shape=[batch, max_len, width],
            dtype="float32", append_batch_size=False)
            for i in range(n_layer)]
        if indexer is not None:
            i_heads, i_dim, i_top_k = indexer
            index_caches = [fluid.layers.data(
                name="index_cache_%d" % i, shape=[batch, max_len, i_dim],
                dtype="float32", append_batch_size=False)
                for i in range(n_layer)]
        # lookup_table squeezes a trailing size-1 ids dim: [batch, T, 1]
        # ids give [batch, T, d_model]; 0 keeps an axis as it comes
        x = fluid.layers.embedding(
            fluid.layers.reshape(x=fluid.layers.cast(tok, "int64"),
                                 shape=[0, 0, 1] if takes_block
                                 else [batch, 1, 1]),
            size=[vocab_size, d_model],
            param_attr=ParamAttr(name=names["embed"]))
        if takes_block:
            # T is read off the token feed; positions [batch, T] are
            # pos .. pos + T - 1.  What a decoder is handed of the step
            # is of the block's last position
            ones, positions = block_positions(tok, pos, batch)
            final, final_row = last, last_token_rows(ones, batch)
            sized = {"prefill_block": prefill_block(batch, n_head, kv_rank,
                                                    d_rope)}
        else:
            positions = fluid.layers.reshape(x=pos, shape=[batch, 1])
            final = final_row = lambda t: t
            sized = {}

        state_pairs = []
        parts = {"hidden": [], "attn_in": [], "attn_out": [], "top_w": [],
                 "top_idx": [],
                 "counts": [], "moe_in": [], "moe_out": [], "selected": [],
                 "live": []}
        for i, block in enumerate(names["blocks"]):
            h = norm(x, eps, block["input_norm"])
            parts["attn_in"].append(final(h))
            c_q = norm(linear(h, q_rank, block["w_dq"]), eps,
                       block["q_norm"])
            q_nope = linear(c_q, n_head * d_nope, block["w_uq_nope"])
            q_rope = rotate(linear(c_q, n_head * d_rope, block["w_uq_rope"]),
                            n_head)
            c, r = fluid.layers.split(
                linear(h, width, block["w_dkv"]), [kv_rank, d_rope], dim=-1)
            chosen = {}
            if indexer is not None:
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
                parts["selected"].append(selected)
                parts["live"].append(live)
            o, cache_out = fluid.layers.mla_cached_attention(
                q_nope, q_rope, norm(c, eps, block["kv_norm"]),
                rotate(r, 1), caches[i],
                pos, n_head, d_v, uk_attr=ParamAttr(name=block["w_uk"]),
                uv_attr=ParamAttr(name=block["w_uv"]), sm_scale=sm_scale,
                **chosen, **sized)
            state_pairs.append(("latent_cache_%d" % i, cache_out.name))
            if indexer is not None:
                state_pairs.append(("index_cache_%d" % i, index_out.name))
            o = linear(o, d_model, block["wo"])
            parts["attn_out"].append(final(o))
            a = x + (norm(o, eps, block["post_attn_norm"])
                     if sandwich_norm else o)
            u = norm(a, eps, block["pre_mlp_norm"])
            f, routing = share_feed_forward(
                u, block, i < n_dense, d_ff, d_expert, n_experts, held,
                top_k, norm_topk, routed_scale, router_bias, n_group,
                topk_group)
            for key, value in (routing or {}).items():
                if key != "counts":     # the whole block's, as it comes
                    value = (final_row if key in ("top_w", "top_idx")
                             else final)(value)
                parts[key].append(value)
            x = a + (norm(f, eps, block["post_mlp_norm"])
                     if sandwich_norm else f)
            parts["hidden"].append(final(x))

        # the head reads the block's last position alone
        logits = fluid.layers.reshape(
            x=linear(norm(final(x), eps, names["norm_f"]), vocab_size,
                     names["head"]),
            shape=[batch, vocab_size])
        pos_out = pos + fluid.layers.reduce_sum(ones) if takes_block \
            else fluid.layers.increment(pos, value=1, in_place=False)
        state_pairs.append(("pos", pos_out.name))
    return main, startup, logits, state_pairs, parts
