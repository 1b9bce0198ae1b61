"""One chip's share of a latent-attention mixture-of-experts decoder as a
cached decode step Program: openPangu-Ultra-MoE-718B
(huggingface.co/FreedomIntelligence/openPangu-Ultra-MoE-718B), the
DeepSeek-V3 family's block (arXiv:2412.19437) with sandwich norms.

One token in, the next token's logits out, one cache of latents a layer
through the `mla_cached_attention` op (ops/attention.py): `c` and the
rotated shared key `r` side by side, `kv_rank + d_rope` values a token,
and no head's key or value.  The feed-forward is dense in the first
`n_dense` layers and after them a shared expert beside a routed layer
(`fluid.layers.moe`: sigmoid scores, the chosen weights normalised and
scaled) that holds the experts `held` = (first, count) of the
`n_experts` its router scores: what one chip of an expert-parallel
deployment computes, with no exchange and nothing that stands in for the
other chips.  Every sub-layer's output is normed before it is added
(`sandwich_norm`).  `fluid.ProgramDecoder` scans the step; prefill is
its scan over the prompt.  The equations are in
`models/reference/pangu_moe.py`, which the tests hold this to.
"""

from .. import fluid
from ..fluid.param_attr import ParamAttr
from .decoder_block import gated_feed_forward, linear, norm

__all__ = ["build_latent_moe_cached_step_program", "latent_moe_param_names"]

_ATTENTION = ("input_norm", "w_dq", "q_norm", "w_uq_nope", "w_uq_rope",
              "w_dkv", "kv_norm", "w_uk", "w_uv", "wo", "post_attn_norm",
              "pre_mlp_norm")
_DENSE = ("ffn_in", "ffn_out")
_EXPERTS = ("shared_in", "shared_out", "router", "w_gate", "w_up", "w_down")


def latent_moe_param_names(n_layer, n_dense):
    """The parameters' names, laid out as the reference's `params`."""
    def block(i):
        kinds = _ATTENTION + (_DENSE if i < n_dense else _EXPERTS) \
            + ("post_mlp_norm",)
        return {w: "block_%d.%s" % (i, w) for w in kinds}

    return {"embed": "embed.w", "blocks": [block(i) for i in range(n_layer)],
            "norm_f": "norm_f", "head": "head.w"}


def build_latent_moe_cached_step_program(
        batch, max_len, vocab_size, n_layer=2, n_dense=1, n_head=4,
        d_model=64, q_rank=32, kv_rank=16, d_nope=16, d_rope=8, d_v=16,
        d_ff=128, d_expert=32, n_experts=8, held=None, top_k=2,
        norm_topk=True, routed_scale=2.5, eps=1e-5, rope_theta=1e4):
    """Returns (main, startup, logits, state_pairs, parts): feeds "tok"
    int32 [batch], "pos" int64 [batch] and "latent_cache_<i>" [batch,
    max_len, kv_rank + d_rope] a layer (declared float32; a feed is taken
    in the type it arrives in, and the op casts a new entry to the
    cache's); `logits` [batch, vocab_size];
    `state_pairs` wires the caches and the position into
    `fluid.ProgramDecoder` (pass max_positions=max_len).  `parts` holds,
    per expert layer, the router's Variables "top_w" and "top_idx", the
    experts' "counts", and the routed layer's input "moe_in" and its
    held experts' part "moe_out" [batch, 1, d_model]; and per layer
    "hidden", the layer's output [batch, 1, d_model]."""
    names = latent_moe_param_names(n_layer, n_dense)
    width = kv_rank + d_rope
    main = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(main, startup):
        tok = fluid.layers.data(name="tok", shape=[batch], dtype="int32",
                                append_batch_size=False)
        pos = fluid.layers.data(name="pos", shape=[batch], dtype="int64",
                                append_batch_size=False)
        caches = [fluid.layers.data(
            name="latent_cache_%d" % i, shape=[batch, max_len, width],
            dtype="float32", append_batch_size=False)
            for i in range(n_layer)]
        # lookup_table squeezes a trailing size-1 ids dim
        x = fluid.layers.embedding(
            fluid.layers.reshape(x=fluid.layers.cast(tok, "int64"),
                                 shape=[batch, 1, 1]),
            size=[vocab_size, d_model],
            param_attr=ParamAttr(name=names["embed"]))
        positions = fluid.layers.reshape(x=pos, shape=[batch, 1])

        state_pairs = []
        parts = {"hidden": [], "top_w": [], "top_idx": [], "counts": [],
                 "moe_in": [], "moe_out": []}
        for i, block in enumerate(names["blocks"]):
            h = norm(x, eps, block["input_norm"])
            c_q = norm(linear(h, q_rank, block["w_dq"]), eps,
                       block["q_norm"])
            q_nope = linear(c_q, n_head * d_nope, block["w_uq_nope"])
            q_rope = fluid.layers.rope(
                linear(c_q, n_head * d_rope, block["w_uq_rope"]),
                positions, n_head, rope_theta)
            c, r = fluid.layers.split(
                linear(h, width, block["w_dkv"]), [kv_rank, d_rope], dim=-1)
            o, cache_out = fluid.layers.mla_cached_attention(
                q_nope, q_rope, norm(c, eps, block["kv_norm"]),
                fluid.layers.rope(r, positions, 1, rope_theta), caches[i],
                pos, n_head, d_v, uk_attr=ParamAttr(name=block["w_uk"]),
                uv_attr=ParamAttr(name=block["w_uv"]))
            state_pairs.append(("latent_cache_%d" % i, cache_out.name))
            a = x + norm(linear(o, d_model, block["wo"]), eps,
                         block["post_attn_norm"])
            u = norm(a, eps, block["pre_mlp_norm"])
            if i < n_dense:
                f = gated_feed_forward(u, d_ff, {"w_in": block["ffn_in"],
                                                 "w_out": block["ffn_out"]})
            else:
                m, _, _, routing = fluid.layers.moe(
                    u, n_experts, d_expert, top_k,
                    *(ParamAttr(name=block[w])
                      for w in ("router", "w_gate", "w_up", "w_down")),
                    scoring="sigmoid", norm_topk=norm_topk,
                    scale=routed_scale,
                    held=held)
                f = gated_feed_forward(
                    u, d_expert, {"w_in": block["shared_in"],
                                  "w_out": block["shared_out"]}) + m
                for key in ("top_w", "top_idx", "counts"):
                    parts[key].append(routing[key])
                parts["moe_in"].append(u)
                parts["moe_out"].append(m)
            x = a + norm(f, eps, block["post_mlp_norm"])
            parts["hidden"].append(x)

        logits = fluid.layers.reshape(
            x=linear(norm(x, eps, names["norm_f"]), vocab_size,
                     names["head"]),
            shape=[batch, vocab_size])
        pos_out = fluid.layers.increment(pos, value=1, in_place=False)
        state_pairs.append(("pos", pos_out.name))
    return main, startup, logits, state_pairs, parts
