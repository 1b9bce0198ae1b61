"""One chip's share of a decoder that mixes linear-attention layers
(the gated delta rule) and attention layers over a cache, as a cached
decode step Program, three families' blocks from one builder:
Qwen3-Next-80B-A3B's
(huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct, `model_type`
`qwen3_next`: Gated DeltaNet beside gated full attention, every layer
with routed experts beside a shared expert, softmax routing),
Ling-3.0-flash's (huggingface.co/inclusionAI/Ling-3.0-flash,
`model_type` `bailing_hybrid`: Kimi Delta Attention, the rule under a
gate a key channel, beside latent attention, sigmoid routing inside the
best groups, leading dense layers; "Ling-3.0-flash's options" below) and
Olmo-Hybrid-7B's (huggingface.co/allenai/Olmo-Hybrid-7B, `model_type`
`olmo_hybrid`: Gated DeltaNet over a 96 x 192 state with beta in (0,
2), beside ungated full attention without positions, a dense
feed-forward on every layer and no router, each sub-layer's output
normed; "Olmo-Hybrid's options" below).

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

Olmo-Hybrid's options (`norm_order="post"`, `qk_norm="whole"`,
`attn_gate=False`, `rope_theta=None`, `beta_scale=2`, `n_dense` = every
layer): the block norms a sub-layer's **output** and not its input, a
= x + N(mixer(x)), y = a + N(ffn(a)) (`post_attn_norm`,
`post_ffn_norm`; the mixer reads the float32 stream cast to the weights'
type); every layer's feed-forward is the dense gated one of width
`d_ff`, so the Program holds no `moe_*` op and a call carries no expert
state.

a `linear_attention` layer is the one above with beta = `beta_scale` *
sigmoid(b) (the family's `allow_neg_eigval`: beta in (0, 2), a step's
transition `I - beta k k^T` with an eigenvalue in (-1, 1)), a key head a
value head, and a state whose value_dim need not be its key_dim.  Where
`value_dim` fills no whole lane blocks (192), "delta_state_<i>" holds
`state_pack` heads side by side, [batch, value heads / 2, key_dim, 2 *
value_dim] (kernels/gdn_step.py `state_pack`, `pack_state`: the state
is not padded in HBM, and the step kernel works it as it lies);
`parts["delta_state"]` is taken apart again, the state as the
recurrence has it.

a `full_attention` layer under these options has no gate ([q] = h W_q),
norms q and k over their **whole projection** (`q_norm` [n_head *
d_head], `k_norm` [n_kv_head * d_head], named `mha_attn`), and rotates
nothing (`rope_theta=None`: the convolution and the recurrence of the
linear layers carry order).  Where every layer is dense the ops between
a feed-forward's two products are named `dense_ffn`.

The equations are in `models/reference/qwen3_next.py`,
`models/reference/ling3_flash.py` and `models/reference/olmo_hybrid.py`,
which the tests hold this to.
"""

from .. import fluid
from ..fluid.initializer import LogScale
from ..fluid.param_attr import ParamAttr
from ..kernels import gdn_step
from .decoder_block import (block_positions, last, last_token_rows, linear,
                            norm, share_feed_forward)
from .latent_moe_program import prefill_block

__all__ = ["build_linear_moe_cached_step_program", "linear_moe_param_names",
           "LINEAR", "FULL", "LATENT"]

LINEAR, FULL, LATENT = ("linear_attention", "full_attention",
                        "latent_attention")
_NORMS = {"pre": ("input_norm", "pre_mlp_norm"),
          "post": ("post_attn_norm", "post_ffn_norm")}
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
                           shared_gate=True, router_bias=False,
                           norm_order="pre"):
    """The parameters' names, laid out as the reference's `params`: a
    linear layer's under the gate it has, the first `n_dense` layers'
    dense feed-forward, the shared expert's gate and the router's bias
    where the options ask for them, a layer's two norms by where they
    stand (`norm_order`)."""
    experts = _EXPERTS + (("shared_gate",) if shared_gate else ()) \
        + (("router_bias",) if router_bias else ())
    return {"embed": "embed.w",
            "blocks": [{w: "block_%d.%s" % (i, w)
                        for w in _NORMS[norm_order]
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
        topk_group=0, kv_rank=16, d_nope=16, d_rope=8, d_v=16,
        norm_order="pre", qk_norm="head", attn_gate=True, beta_scale=1.0,
        state_pack=None):
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

    `norm_order` ("pre": a sub-layer's input is normed; "post": its
    output is, Olmo's), `qk_norm` ("head", or "whole": over the whole
    projection), `attn_gate`, `rope_theta` (None: no rotation),
    `beta_scale` (2: beta in (0, 2)) and `state_pack` (the value heads
    side by side in a unit of "delta_state_<i>", [batch, value_heads /
    p, key_dim, p * value_dim]; None: `gdn_step.state_pack`'s choice,
    1 under a gate a key channel) are the module docstring's
    "Olmo-Hybrid's options"; with `n_dense` = every layer there is no
    router.

    `parts` are **of the block's last position**, in shapes that T does
    not change, as the window builder's: per layer "hidden", "attn_in"
    and "attn_out" (the mixer's normed input and its output after
    `wo`); per layer the router's "top_w" and "top_idx" [batch, top_k],
    "moe_in" and the held experts' part "moe_out" [batch, 1, d_model],
    and "counts"; and with `state_rows` > 0, per linear layer
    "delta_state", the first `state_rows` rows of the state the step
    hands on (what a caller can afford to read back of 2 MB a row and
    layer), the heads apart whatever `state_pack`: [state_rows, value
    heads, key_dim, value_dim]."""
    if set(layer_types) - {LINEAR, FULL, LATENT} \
            or gate not in ("head", "channel") \
            or norm_order not in _NORMS or qk_norm not in ("head", "whole"):
        raise ValueError("linear_moe: layer_types %s are not %s / %s / %s, "
                         "the gate %r is not a head's or a channel's, the "
                         "norms stand neither %r a sub-layer nor %r it, or "
                         "q and k are normed neither a %r nor %r"
                         % (layer_types, LINEAR, FULL, LATENT, gate, "pre",
                            "post", "head", "whole"))
    names = linear_moe_param_names(layer_types, n_dense, gate, shared_gate,
                                   router_bias, norm_order)
    if state_pack is None:
        state_pack = 1 if gate == "channel" \
            else gdn_step.state_pack(value_heads, value_dim)
    # a model whose every layer is dense has no router: its
    # feed-forward's ops are named, for a trace's readers
    dense_name = "dense_ffn" if n_dense >= len(layer_types) else None
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
             feed("delta_state_%d" % i,
                  [batch, value_heads // state_pack, key_dim,
                   state_pack * value_dim])]
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
                *qkv, g, beta, states[i][1], chunk=chunk,
                state_pack=state_pack, **gate)
            state_pairs.append(("conv_tail_%d" % i, tail_out.name))
            state_pairs.append(("delta_state_%d" % i, state_out.name))
            if state_rows:
                kept = fluid.layers.slice(
                    state_out, axes=[0], starts=[0], ends=[state_rows])
                if state_pack > 1:
                    # the heads apart again, as the recurrence has them
                    kept = fluid.layers.reshape(
                        fluid.layers.transpose(
                            fluid.layers.reshape(
                                kept, [state_rows,
                                       value_heads // state_pack, key_dim,
                                       state_pack, value_dim]),
                            [0, 1, 3, 2, 4]),
                        [state_rows, value_heads, key_dim, value_dim])
                parts["delta_state"].append(kept)
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
            if beta_scale != 1.0:
                beta = fluid.layers.scale(beta, scale=float(beta_scale),
                                          **gates)
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

        def qk_normed(t, heads, scale):
            """q or k RMS-normed a head, or over the whole projection
            (one scale an output column; named for a trace's readers)."""
            if qk_norm == "head":
                return head_norm(t, heads, d_head, scale)
            return fluid.layers.rms_norm(
                t, epsilon=eps, param_attr=ParamAttr(name=scale),
                name="mha_attn")

        def full_mixer(i, h, block):
            if attn_gate:
                # a head's query values, then its gate values
                q, gate = (fluid.layers.reshape(t, [0, 0, n_head * d_head])
                           for t in fluid.layers.split(
                               fluid.layers.reshape(
                                   linear(h, 2 * n_head * d_head,
                                          block["wq"]),
                                   [0, 0, n_head, 2 * d_head]), 2, dim=-1))
            else:
                q = linear(h, n_head * d_head, block["wq"])
            q = qk_normed(q, n_head, block["q_norm"])
            k = qk_normed(linear(h, n_kv_head * d_head, block["wk"]),
                          n_kv_head, block["k_norm"])
            v = linear(h, n_kv_head * d_head, block["wv"])
            if rope_theta is not None:
                q = fluid.layers.rope(q, positions, n_head, rope_theta,
                                      rotary_dim=rotary_dim)
                k = fluid.layers.rope(k, positions, n_kv_head, rope_theta,
                                      rotary_dim=rotary_dim)
            o, k_out, v_out = fluid.layers.cached_attention(
                q, k, v, states[i][0], states[i][1], pos, num_heads=n_head,
                num_kv_heads=n_kv_head)
            state_pairs.append(("k_cache_%d" % i, k_out.name))
            state_pairs.append(("v_cache_%d" % i, v_out.name))
            if not attn_gate:
                return o
            return fluid.layers.elementwise_mul(
                o, fluid.layers.sigmoid(gate, name="attn_gate"),
                name="attn_gate")

        state_pairs = []
        parts = {"hidden": [], "attn_in": [], "attn_out": [], "top_w": [],
                 "top_idx": [], "counts": [], "moe_in": [], "moe_out": [],
                 "delta_state": []}
        pre = norm_order == "pre"

        def entering(t, name):
            """What a sub-layer reads of the float32 stream: its norm,
            or under `norm_order="post"` the stream itself, in the
            weights' type."""
            return normed(t, name) if pre \
                else fluid.layers.cast(t, embedded)

        def leaving(t, name):
            """What a sub-layer adds to the stream, float32: its output,
            or under `norm_order="post"` the output's norm."""
            t = fluid.layers.cast(t, "float32")
            return t if pre else norm(t, eps, name)

        for i, block in enumerate(names["blocks"]):
            attn_norm, ffn_norm = (block[w] for w in _NORMS[norm_order])
            h = entering(x, attn_norm)
            parts["attn_in"].append(last(h))
            mixer = {LINEAR: channel_mixer if gate == "channel"
                     else linear_mixer, FULL: full_mixer,
                     LATENT: latent_mixer}[layer_types[i]]
            o = linear(mixer(i, h, block), d_model, block["wo"])
            parts["attn_out"].append(last(o))
            a = x + leaving(o, attn_norm)
            u = entering(a, ffn_norm)
            f, routing = share_feed_forward(
                u, block, i < n_dense, d_ff, d_expert, n_experts, held,
                top_k, norm_topk, routed_scale, router_bias, n_group,
                topk_group, scoring=scoring,
                shared_gate=block.get("shared_gate"),
                dense_name=dense_name)
            for key, value in (routing or {}).items():
                if key != "counts":     # the whole block's, as it comes
                    value = (last_row if key in ("top_w", "top_idx")
                             else last)(value)
                parts[key].append(value)
            x = a + leaving(f, ffn_norm)
            parts["hidden"].append(last(x))

        # the head reads the block's last position alone
        logits = fluid.layers.reshape(
            x=linear(normed(last(x), names["norm_f"]), vocab_size,
                     names["head"]),
            shape=[batch, vocab_size])
        pos_out = pos + fluid.layers.reduce_sum(ones)
        state_pairs.append(("pos", pos_out.name))
    return main, startup, logits, state_pairs, parts
