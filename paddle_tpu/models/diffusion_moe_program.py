"""A pipeline stage of a block-diffusion expert decoder as a cached
step Program: SDAR-30B-A3B-Chat's layer
(huggingface.co/JetLM/SDAR-30B-A3B-Chat, `model_type` `sdar_moe`), the
Qwen3-MoE block under a **block-causal** mask.

A block of T consecutive tokens of every row in, T a multiple of the
diffusion block's length B; **the logits of every position fed** out,
[batch, T, vocab], row i predicting position i's own token (a masked
position's token: no shift); two caches a layer, keys and values of the
whole extent ("k_cache_<i>", "v_cache_<i>" [batch, n_kv_head, max_len,
d_head]) through `cached_attention` with `diffusion_block` B: position i
attends every slot up to the end of its own block of B, so a pass over
one block (T = B) sees all of it, both directions, behind everything
stored before it, and a prompt's prefill (T = 128) is 32 whole blocks.
q and k are RMS-normed head by head and turned rotate-half at the
token's position; the experts are routed by a softmax over all of them,
the chosen probabilities divided by their sum, no shared expert, all of
them held (`held` None) or a range.  The residual stream is float32,
the block pre-norm, as `models/window_moe_program.py` has them (read
there why).

`fluid.ProgramDecoder.diffuse` runs it (`models/decode.py
block_diffusion_decode`): denoising passes, whose logits it reads and
whose slots the next pass overwrites, and commit passes and the prefill,
whose logits nothing reads, so that the head is dead code in them.

This is a builder of its own beside `models/sparse_kv_moe_program.py`,
whose layer it is without the chooser: that builder's three caches a
layer, three-part positions, `rope_delta` and probes of a block's last
position would each have forked on an `indexer=None`, and its logits are
of the last position alone.  What the two share is `decoder_block`
(`head_norm`, `share_feed_forward`, `block_positions`).

The equations are in `models/reference/sdar_moe.py`, which the tests
hold this to.
"""

from .. import fluid
from ..fluid.param_attr import ParamAttr
from .decoder_block import (block_positions, head_norm, linear, norm,
                            share_feed_forward)

__all__ = ["build_diffusion_moe_cached_step_program",
           "diffusion_moe_param_names"]

_BLOCK = ("input_norm", "wq", "wk", "wv", "q_norm", "k_norm", "wo",
          "pre_mlp_norm", "router", "w_gate", "w_up", "w_down")


def diffusion_moe_param_names(n_layer):
    """The parameters' names, laid out as the reference's `params`."""
    return {"embed": "embed.w",
            "blocks": [{w: "block_%d.%s" % (i, w) for w in _BLOCK}
                       for i in range(n_layer)],
            "norm_f": "norm_f", "head": "head.w"}


def build_diffusion_moe_cached_step_program(
        batch, max_len, vocab_size, block_length, n_layer=2, n_head=4,
        n_kv_head=2, d_head=16, d_model=64, d_expert=32, n_experts=8,
        held=None, top_k=2, norm_topk=True, eps=1e-6, rope_theta=1e6,
        probe_rows=0):
    """Returns (main, startup, logits, state_pairs, parts): feeds "tok" int32
    [batch, T] (declared [batch, -1]: T consecutive tokens of every row,
    a multiple of `block_length`, read off the feed), "pos" int64
    [batch], the slot the block's first token writes (rows move in
    lockstep; a multiple of `block_length`), and, a layer, "k_cache_<i>"
    and "v_cache_<i>" [batch, n_kv_head, max_len, d_head] (declared
    float32; a feed is taken in the type it arrives in, and the op casts
    a new entry to the cache's).  `logits` [batch, T, vocab_size], of
    every position fed; `state_pairs` wires the caches and the position
    advanced by T into `fluid.ProgramDecoder` (pass
    max_positions=max_len), whose `diffuse` holds the position back in a
    pass that is not to be kept.  `parts` is, with `probe_rows` > 0,
    {"keys", "values": per layer the first `probe_rows` rows of the cache
    the step hands on, [probe_rows, n_kv_head, max_len, d_head]}: wired
    as a state pair the step only writes, what a decoder carries out of a
    call's last pass for a check to read; {} otherwise."""
    names = diffusion_moe_param_names(n_layer)
    main = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(main, startup):
        def feed(name, shape, dtype):
            return fluid.layers.data(name=name, shape=shape, dtype=dtype,
                                     append_batch_size=False)

        tok = feed("tok", [batch, -1], "int32")
        pos = feed("pos", [batch], "int64")
        caches = [[feed("%s_cache_%d" % (which, i),
                        [batch, n_kv_head, max_len, d_head], "float32")
                   for which in "kv"] for i in range(n_layer)]
        embedded = fluid.layers.embedding(
            fluid.layers.reshape(x=fluid.layers.cast(tok, "int64"),
                                 shape=[0, 0, 1]),
            size=[vocab_size, d_model],
            param_attr=ParamAttr(name=names["embed"]))
        x = fluid.layers.cast(embedded, "float32")
        ones, slots = block_positions(tok, pos, batch)

        def normed(t, name):
            """RMSNorm of the float32 stream, in the weights' type."""
            return fluid.layers.cast(norm(t, eps, name), embedded)

        state_pairs = []
        parts = {"keys": [], "values": []} if probe_rows else {}
        for i, block in enumerate(names["blocks"]):
            h = normed(x, block["input_norm"])
            q = fluid.layers.rope(
                head_norm(linear(h, n_head * d_head, block["wq"]), n_head,
                          d_head, eps, block["q_norm"]),
                slots, n_head, rope_theta)
            k = fluid.layers.rope(
                head_norm(linear(h, n_kv_head * d_head, block["wk"]),
                          n_kv_head, d_head, eps, block["k_norm"]),
                slots, n_kv_head, rope_theta)
            v = linear(h, n_kv_head * d_head, block["wv"])
            o, k_out, v_out = fluid.layers.cached_attention(
                q, k, v, caches[i][0], caches[i][1], pos, num_heads=n_head,
                num_kv_heads=n_kv_head, diffusion_block=block_length)
            state_pairs += [("k_cache_%d" % i, k_out.name),
                            ("v_cache_%d" % i, v_out.name)]
            for key, cache in (("keys", k_out), ("values", v_out)) \
                    if probe_rows else ():
                parts[key].append(fluid.layers.slice(
                    cache, axes=[0], starts=[0], ends=[probe_rows]))
            a = x + fluid.layers.cast(linear(o, d_model, block["wo"]),
                                      "float32")
            f, _ = share_feed_forward(
                normed(a, block["pre_mlp_norm"]), block, False, 0,
                d_expert, n_experts, held, top_k, norm_topk, 1.0,
                scoring="softmax")
            x = a + fluid.layers.cast(f, "float32")

        # the head reads every position fed: a masked position's own row
        # predicts its own token
        logits = linear(normed(x, names["norm_f"]), vocab_size,
                        names["head"])
        pos_out = pos + fluid.layers.reduce_sum(ones)
        state_pairs.append(("pos", pos_out.name))
    return main, startup, logits, state_pairs, parts
