"""One chip's share of a decoder that mixes linear-attention layers
(the gated delta rule) and attention layers over a cache, every layer
with routed experts beside a shared expert, as a cached decode step
Program: Qwen3-Next-80B-A3B's block
(huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct, `model_type`
`qwen3_next`: Gated DeltaNet beside gated full attention, softmax
routing), and Ling-3.0-flash's (huggingface.co/inclusionAI/Ling-3.0-flash,
`model_type` `bailing_hybrid`: Kimi Delta Attention, the rule under a
gate a key channel, beside latent attention, sigmoid routing inside the
best groups, leading dense layers; "Ling-3.0-flash's options" below).

A block of T >= 1 consecutive tokens of every row in (T = 1: a decode
step; a prompt's prefill feeds `models.decode.PREFILL_BLOCK` an
application), the logits after the block's last out, and **two kinds of
state in one step**:

a `linear_attention` layer keeps no entry a position.  It carries the
last `conv_width - 1` positions of its convolution's input
("conv_tail_<i>" [batch, conv_width - 1, 2 * key width + value width],
in the weights' type) and one recurrent state a value head
("delta_state_<i>" [batch, value heads, key_dim, value_dim] float32),
which a step rewrites whole through the `gated_delta_rule` op
(ops/linear_attention.py: one position through kernels/gdn_step.py, a
block in chunks).  With u the layer's normed input: [q | k | v | z] = u
W_qkvz, [b | a] = u W_ba; q, k, v go through the causal depthwise
convolution and a SiLU together (`causal_conv1d` with its tail); beta =
sigmoid(b) and g = -exp(A_log) softplus(a + dt_bias) in float32; the
rule's output is RMS-normed head by head (one learned [value_dim]
scale), times silu(z), and projected by W_o.

a `full_attention` layer keeps keys and values over the whole extent
("k_cache_<i>", "v_cache_<i>" [batch, n_kv_head, max_len, d_head])
through the `cached_attention` op: `n_head` query heads read `n_kv_head`
key/value heads by index; [q | gate] = u W_q, head by head (a head's
`d_head` query values, then its `d_head` gate values); q and k are
RMS-normed over a head's values, the first `rotary_dim` of which are
rotated (rotate-half, `rope_theta`); the attended values times
sigmoid(gate) go through W_o.

The block is pre-norm, two norms a layer, and the residual stream is
float32 whatever the weights' type (as `window_moe_program.py`'s, and
for its reason).  The feed-forward half is
`decoder_block.share_feed_forward` with softmax scoring and a gate on
the shared expert.  Every norm multiplies by its stored scale (the
family stores a scale less one; a seeded scale is drawn about 1).

Ling-3.0-flash's options (`gate="channel"`, `latent_attention` layers,
`n_dense`, `scoring="sigmoid"`): **three kinds of state in one step**.

a `linear_attention` layer under `gate="channel"` is Kimi Delta
Attention (arXiv:2510.26692): [q | k | v | f] = u W_qkvf, [b | z] = u
W_bz; q, k, v through the convolution with its tail and a SiLU as above;
beta = sigmoid(b) and, in float32 (`kda_gates`), g = `gate_floor` *
sigmoid(exp(A_log[head]) (f + dt_bias)), one value a head and key
channel in [`gate_floor`, 0), which the `gated_delta_rule` op takes as
G [batch, T, value heads * key_dim] (one position through the
`kda_step_*` kernel, a block in chunks of sub-blocks sized from
`gate_floor`); the rule's output is RMS-normed head by head, times
sigmoid(z), **one gate a head**, and projected by W_o.  The state pair
is "conv_tail_<i>" and "delta_state_<i>", as above.

a `latent_attention` layer keeps one cache of latents
("latent_cache_<i>" [batch, max_len, kv_rank + d_rope]) through the
`mla_cached_attention` op as `latent_moe_program.py`'s layers do, with
a full-rank query ([q_nope | q_rope] = u W_q, no query latent), the
latent RMS-normed, the shared key and the heads' `d_rope` query values
rotated, and the attended values times sigmoid(u W_z), one gate a head
(`latent_gate`), through W_o.

The first `n_dense` layers' feed-forward is the dense gated one of
width `d_ff`; the others' is `decoder_block.share_feed_forward` with
sigmoid scoring, the choice by score plus `router_bias` inside the best
`topk_group` of `n_group` groups, the chosen scores divided by their sum
and scaled by `routed_scale`, and a shared expert without a gate.

The equations are in `models/reference/qwen3_next.py` and
`models/reference/ling3_flash.py`, which the tests hold this to.
"""

from .. import fluid
from ..fluid.initializer import LogScale
from ..fluid.param_attr import ParamAttr
from .decoder_block import (block_positions, last, last_token_rows, linear,
                            norm, share_feed_forward)
from .latent_moe_program import prefill_block

__all__ = ["build_linear_moe_cached_step_program", "linear_moe_param_names",
           "LINEAR", "FULL", "LATENT"]

LINEAR, FULL, LATENT = ("linear_attention", "full_attention",
                        "latent_attention")
_NORMS = ("input_norm", "pre_mlp_norm")
_DENSE = ("ffn_in", "ffn_out")
_EXPERTS = ("shared_in", "shared_out", "router", "w_gate", "w_up", "w_down")
_MIXER = {LINEAR: ("w_qkvz", "w_ba", "conv", "a_log", "dt_bias", "out_norm",
                   "wo"),
          "channel": ("w_qkvf", "w_bz", "conv", "a_log", "dt_bias",
                      "out_norm", "wo"),
          FULL: ("wq", "wk", "wv", "q_norm", "k_norm", "wo"),
          LATENT: ("wq_nope", "wq_rope", "w_dkv", "kv_norm", "w_uk", "w_uv",
                   "w_z", "wo")}


def linear_moe_param_names(layer_types, n_dense=0, gate="head",
                           shared_gate=True, router_bias=False):
    """The parameters' names, laid out as the reference's `params`: a
    linear layer's under the gate it has, the first `n_dense` layers'
    dense feed-forward, the shared expert's gate and the router's bias
    where the options ask for them."""
    experts = _EXPERTS + (("shared_gate",) if shared_gate else ()) \
        + (("router_bias",) if router_bias else ())
    return {"embed": "embed.w",
            "blocks": [{w: "block_%d.%s" % (i, w)
                        for w in _NORMS
                        + (_DENSE if i < n_dense else experts)
                        + _MIXER["channel" if kind == LINEAR
                                 and gate == "channel" else kind]}
                       for i, kind in enumerate(layer_types)],
            "norm_f": "norm_f", "head": "head.w"}


def build_linear_moe_cached_step_program(
        batch, max_len, vocab_size, layer_types=(LINEAR, FULL), n_head=4,
        n_kv_head=2, d_head=16, rotary_dim=4, key_heads=2, value_heads=4,
        key_dim=8, value_dim=8, conv_width=4, d_model=64, d_expert=32,
        n_experts=8, held=None, top_k=2, norm_topk=True, eps=1e-6,
        rope_theta=1e7, chunk=64, state_rows=0, gate="head",
        gate_floor=-5.0, n_dense=0, d_ff=0, scoring="softmax",
        shared_gate=True, routed_scale=1.0, router_bias=False, n_group=0,
        topk_group=0, kv_rank=16, d_nope=16, d_rope=8, d_v=16):
    """Returns (main, startup, logits, state_pairs, parts): feeds "tok"
    int32 [batch, T] (declared [batch, -1]: T >= 1 consecutive tokens of
    every row, read off the feed), "pos" int64 [batch], the position of
    the block's first token (rows move in lockstep), and the states the
    module's docstring names, a linear layer's two, a full layer's two
    and a latent layer's one (declared float32; a feed is taken in the
    type it arrives in);
    `logits` [batch, vocab_size], of the block's last position alone;
    `state_pairs` wires every state and the position, advanced by T,
    into `fluid.ProgramDecoder` (pass max_positions=max_len).

    `parts` are **of the block's last position**, in shapes that T does
    not change, as the window builder's: per layer "hidden", "attn_in"
    and "attn_out" (the mixer's normed input and its output after
    `wo`); per layer the router's "top_w" and "top_idx" [batch, top_k],
    "moe_in" and the held experts' part "moe_out" [batch, 1, d_model],
    and "counts"; and with `state_rows` > 0, per linear layer
    "delta_state", the first `state_rows` rows of the state the step
    hands on (what a caller can afford to read back of 2 MB a row and
    layer)."""
    if set(layer_types) - {LINEAR, FULL, LATENT} \
            or gate not in ("head", "channel"):
        raise ValueError("linear_moe: layer_types %s are not %s / %s / %s, "
                         "or the gate %r is not a head's or a channel's"
                         % (layer_types, LINEAR, FULL, LATENT, gate))
    names = linear_moe_param_names(layer_types, n_dense, gate, shared_gate,
                                   router_bias)
    key_width, value_width = key_heads * key_dim, value_heads * value_dim
    conv_channels = 2 * key_width + value_width
    main = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(main, startup):
        def feed(name, shape, dtype="float32"):
            return fluid.layers.data(name=name, shape=shape, dtype=dtype,
                                     append_batch_size=False)

        tok = feed("tok", [batch, -1], "int32")
        pos = feed("pos", [batch], "int64")
        states = [
            [feed("conv_tail_%d" % i, [batch, conv_width - 1,
                                       conv_channels]),
             feed("delta_state_%d" % i, [batch, value_heads, key_dim,
                                         value_dim])]
            if kind == LINEAR else
            [feed("latent_cache_%d" % i, [batch, max_len, kv_rank + d_rope])]
            if kind == LATENT else
            [feed("%s_cache_%d" % (which, i),
                  [batch, n_kv_head, max_len, d_head]) for which in "kv"]
            for i, kind in enumerate(layer_types)]
        embedded = fluid.layers.embedding(
            fluid.layers.reshape(x=fluid.layers.cast(tok, "int64"),
                                 shape=[0, 0, 1]),
            size=[vocab_size, d_model],
            param_attr=ParamAttr(name=names["embed"]))
        x = fluid.layers.cast(embedded, "float32")

        def normed(t, name):
            """RMSNorm of the float32 stream, in the weights' type."""
            return fluid.layers.cast(norm(t, eps, name), embedded)

        ones, positions = block_positions(tok, pos, batch)

        def head_norm(t, heads, width, scale, **kwargs):
            """RMSNorm over each head's `width` values."""
            t = fluid.layers.rms_norm(
                fluid.layers.reshape(t, [0, 0, heads, width]), epsilon=eps,
                param_attr=ParamAttr(name=scale), **kwargs)
            return fluid.layers.reshape(t, [0, 0, heads * width])

        last_row = last_token_rows(ones, batch)

        def convolved(i, qkv, block):
            """q, k, v of [q | k | v] through the convolution that
            carries layer i's tail, and the tail it hands on."""
            qkv, tail_out = fluid.layers.causal_conv1d(
                qkv, conv_width, "silu",
                param_attr=ParamAttr(name=block["conv"]), bias_attr=False,
                tail=states[i][0])
            return fluid.layers.split(
                qkv, [key_width, key_width, value_width], dim=-1), tail_out

        def ruled(i, qkv, g, beta, tail_out, **gate):
            """The rule's output for layer i, its two states wired into
            the decoder and the carried rows of the new state kept."""
            o, state_out = fluid.layers.gated_delta_rule(
                *qkv, g, beta, states[i][1], chunk=chunk, **gate)
            state_pairs.append(("conv_tail_%d" % i, tail_out.name))
            state_pairs.append(("delta_state_%d" % i, state_out.name))
            if state_rows:
                parts["delta_state"].append(fluid.layers.slice(
                    state_out, axes=[0], starts=[0], ends=[state_rows]))
            return o

        def head_gated(y, z, heads, width, name):
            """y [batch, T, heads, width] times sigmoid(z) [batch, T,
            heads], a gate a head -> [batch, T, heads * width]."""
            return fluid.layers.reshape(
                fluid.layers.elementwise_mul(
                    y, fluid.layers.sigmoid(
                        fluid.layers.reshape(z, [0, 0, heads, 1],
                                             name=name), name=name),
                    name=name), [0, 0, heads * width], name=name)

        def linear_mixer(i, h, block):
            qkv, z = fluid.layers.split(
                linear(h, conv_channels + value_width, block["w_qkvz"]),
                [conv_channels, value_width], dim=-1)
            b, a = fluid.layers.split(
                linear(h, 2 * value_heads, block["w_ba"]), 2, dim=-1)
            qkv, tail_out = convolved(i, qkv, block)
            # the gates, float32 from the projection on (named: the ops'
            # instances in a trace start with it)
            rate, dt_bias = (fluid.layers.create_parameter(
                [value_heads], "float32", attr=ParamAttr(name=block[w]),
                default_initializer=init) for w, init in (
                    ("a_log", LogScale(1e-3, 16.0, "log_uniform")),
                    ("dt_bias", LogScale(
                        1e-3, 1e-1, "inverse_softplus_log_uniform"))))
            gates = {"name": "gdn_gates"}
            beta = fluid.layers.sigmoid(
                fluid.layers.cast(b, "float32", **gates), **gates)
            g = fluid.layers.elementwise_mul(
                fluid.layers.softplus(fluid.layers.elementwise_add(
                    fluid.layers.cast(a, "float32", **gates), dt_bias,
                    **gates), **gates),
                fluid.layers.scale(fluid.layers.exp(rate, **gates),
                                   scale=-1.0, **gates), **gates)
            o = ruled(i, qkv, g, beta, tail_out)
            y = head_norm(o, value_heads, value_dim, block["out_norm"],
                          name="gdn_out_norm")
            return fluid.layers.elementwise_mul(
                y, fluid.layers.swish(z, name="gdn_out_norm"),
                name="gdn_out_norm")

        def channel_mixer(i, h, block):
            """Kimi Delta Attention: the rule under a gate a key
            channel, its output gated a head."""
            gate_width = value_heads * key_dim
            qkv, f = fluid.layers.split(
                linear(h, conv_channels + gate_width, block["w_qkvf"]),
                [conv_channels, gate_width], dim=-1)
            b, z = fluid.layers.split(
                linear(h, 2 * value_heads, block["w_bz"]), 2, dim=-1)
            qkv, tail_out = convolved(i, qkv, block)
            rate = fluid.layers.create_parameter(
                [value_heads], "float32", attr=ParamAttr(name=block["a_log"]),
                default_initializer=LogScale(1e-3, 16.0, "log_uniform"))
            dt_bias = fluid.layers.create_parameter(
                [gate_width], "float32",
                attr=ParamAttr(name=block["dt_bias"]),
                default_initializer=LogScale(
                    1e-3, 1e-1, "inverse_softplus_log_uniform"))
            gates = {"name": "kda_gates"}
            beta = fluid.layers.sigmoid(
                fluid.layers.cast(b, "float32", **gates), **gates)
            # g = gate_floor * sigmoid(exp(A_log[head]) (f + dt_bias)),
            # float32 from the projection on
            rated = fluid.layers.elementwise_mul(
                fluid.layers.reshape(
                    fluid.layers.elementwise_add(
                        fluid.layers.cast(f, "float32", **gates), dt_bias,
                        **gates), [0, 0, value_heads, key_dim], **gates),
                fluid.layers.reshape(fluid.layers.exp(rate, **gates),
                                     [value_heads, 1], **gates), **gates)
            g = fluid.layers.reshape(
                fluid.layers.scale(fluid.layers.sigmoid(rated, **gates),
                                   scale=float(gate_floor), **gates),
                [0, 0, gate_width], **gates)
            o = ruled(i, qkv, g, beta, tail_out, gate_floor=gate_floor)
            y = fluid.layers.rms_norm(
                fluid.layers.reshape(o, [0, 0, value_heads, value_dim]),
                epsilon=eps, param_attr=ParamAttr(name=block["out_norm"]),
                name="kda_out_norm")
            return head_gated(y, z, value_heads, value_dim, "kda_out_norm")

        def latent_mixer(i, h, block):
            """Latent attention with a full-rank query, gated a head."""
            c, r = fluid.layers.split(
                linear(h, kv_rank + d_rope, block["w_dkv"]),
                [kv_rank, d_rope], dim=-1)
            rotate = lambda t, heads: fluid.layers.rope(
                t, positions, heads, rope_theta, full_width=True)
            o, cache_out = fluid.layers.mla_cached_attention(
                linear(h, n_head * d_nope, block["wq_nope"]),
                rotate(linear(h, n_head * d_rope, block["wq_rope"]), n_head),
                norm(c, eps, block["kv_norm"]), rotate(r, 1), states[i][0],
                pos, n_head, d_v, uk_attr=ParamAttr(name=block["w_uk"]),
                uv_attr=ParamAttr(name=block["w_uv"]),
                # a prompt is prefilled a chunk of the rule an
                # application at most: under a gate a key channel the
                # chunk's float32 products (the sub-blocks' right
                # factors, [rows, T, heads, chunk / sub, key_dim]) are
                # the step's largest temporaries, a GB a layer at 128
                # rows x 128 positions
                prefill_block=min(prefill_block(batch, n_head, kv_rank,
                                                d_rope), chunk))
            state_pairs.append(("latent_cache_%d" % i, cache_out.name))
            return head_gated(
                fluid.layers.reshape(o, [0, 0, n_head, d_v]),
                linear(h, n_head, block["w_z"]), n_head, d_v, "latent_gate")

        def full_mixer(i, h, block):
            # a head's query values, then its gate values
            q, gate = (fluid.layers.reshape(t, [0, 0, n_head * d_head])
                       for t in fluid.layers.split(
                           fluid.layers.reshape(
                               linear(h, 2 * n_head * d_head, block["wq"]),
                               [0, 0, n_head, 2 * d_head]), 2, dim=-1))
            q = head_norm(q, n_head, d_head, block["q_norm"])
            k = head_norm(linear(h, n_kv_head * d_head, block["wk"]),
                          n_kv_head, d_head, block["k_norm"])
            v = linear(h, n_kv_head * d_head, block["wv"])
            q = fluid.layers.rope(q, positions, n_head, rope_theta,
                                  rotary_dim=rotary_dim)
            k = fluid.layers.rope(k, positions, n_kv_head, rope_theta,
                                  rotary_dim=rotary_dim)
            o, k_out, v_out = fluid.layers.cached_attention(
                q, k, v, states[i][0], states[i][1], pos, num_heads=n_head,
                num_kv_heads=n_kv_head)
            state_pairs.append(("k_cache_%d" % i, k_out.name))
            state_pairs.append(("v_cache_%d" % i, v_out.name))
            return fluid.layers.elementwise_mul(
                o, fluid.layers.sigmoid(gate, name="attn_gate"),
                name="attn_gate")

        state_pairs = []
        parts = {"hidden": [], "attn_in": [], "attn_out": [], "top_w": [],
                 "top_idx": [], "counts": [], "moe_in": [], "moe_out": [],
                 "delta_state": []}
        for i, block in enumerate(names["blocks"]):
            h = normed(x, block["input_norm"])
            parts["attn_in"].append(last(h))
            mixer = {LINEAR: channel_mixer if gate == "channel"
                     else linear_mixer, FULL: full_mixer,
                     LATENT: latent_mixer}[layer_types[i]]
            o = linear(mixer(i, h, block), d_model, block["wo"])
            parts["attn_out"].append(last(o))
            a = x + fluid.layers.cast(o, "float32")
            u = normed(a, block["pre_mlp_norm"])
            f, routing = share_feed_forward(
                u, block, i < n_dense, d_ff, d_expert, n_experts, held,
                top_k, norm_topk, routed_scale, router_bias, n_group,
                topk_group, scoring=scoring,
                shared_gate=block.get("shared_gate"))
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
