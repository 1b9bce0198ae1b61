"""One chip's share of a decoder that mixes linear-attention layers
(Gated DeltaNet) and gated full-attention layers, every layer with
softmax-routed experts beside a sigmoid-gated shared expert, as a cached
decode step Program: Qwen3-Next-80B-A3B's block
(huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct, `model_type`
`qwen3_next`).

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

The equations are in `models/reference/qwen3_next.py`, which the tests
hold this to.
"""

from .. import fluid
from ..fluid.initializer import LogScale
from ..fluid.param_attr import ParamAttr
from .decoder_block import (block_positions, last, last_token_rows, linear,
                            norm, share_feed_forward)

__all__ = ["build_linear_moe_cached_step_program", "linear_moe_param_names",
           "LINEAR", "FULL"]

LINEAR, FULL = "linear_attention", "full_attention"
_SHARED = ("input_norm", "pre_mlp_norm", "shared_in", "shared_out",
           "shared_gate", "router", "w_gate", "w_up", "w_down")
_MIXER = {LINEAR: ("w_qkvz", "w_ba", "conv", "a_log", "dt_bias", "out_norm",
                   "wo"),
          FULL: ("wq", "wk", "wv", "q_norm", "k_norm", "wo")}


def linear_moe_param_names(layer_types):
    """The parameters' names, laid out as the reference's `params`."""
    return {"embed": "embed.w",
            "blocks": [{w: "block_%d.%s" % (i, w)
                        for w in _SHARED + _MIXER[kind]}
                       for i, kind in enumerate(layer_types)],
            "norm_f": "norm_f", "head": "head.w"}


def build_linear_moe_cached_step_program(
        batch, max_len, vocab_size, layer_types=(LINEAR, FULL), n_head=4,
        n_kv_head=2, d_head=16, rotary_dim=4, key_heads=2, value_heads=4,
        key_dim=8, value_dim=8, conv_width=4, d_model=64, d_expert=32,
        n_experts=8, held=None, top_k=2, norm_topk=True, eps=1e-6,
        rope_theta=1e7, chunk=64, state_rows=0):
    """Returns (main, startup, logits, state_pairs, parts): feeds "tok"
    int32 [batch, T] (declared [batch, -1]: T >= 1 consecutive tokens of
    every row, read off the feed), "pos" int64 [batch], the position of
    the block's first token (rows move in lockstep), and the states the
    module's docstring names, a linear layer's two and a full layer's
    two (declared float32; a feed is taken in the type it arrives in);
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
    if set(layer_types) - {LINEAR, FULL}:
        raise ValueError("linear_moe: layer_types %s are not %s / %s"
                         % (layer_types, LINEAR, FULL))
    names = linear_moe_param_names(layer_types)
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

        def linear_mixer(i, h, block):
            tail, state = states[i]
            qkv, z = fluid.layers.split(
                linear(h, conv_channels + value_width, block["w_qkvz"]),
                [conv_channels, value_width], dim=-1)
            b, a = fluid.layers.split(
                linear(h, 2 * value_heads, block["w_ba"]), 2, dim=-1)
            qkv, tail_out = fluid.layers.causal_conv1d(
                qkv, conv_width, "silu",
                param_attr=ParamAttr(name=block["conv"]), bias_attr=False,
                tail=tail)
            q, k, v = fluid.layers.split(
                qkv, [key_width, key_width, value_width], dim=-1)
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
            o, state_out = fluid.layers.gated_delta_rule(
                q, k, v, g, beta, state, chunk=chunk)
            state_pairs.append(("conv_tail_%d" % i, tail_out.name))
            state_pairs.append(("delta_state_%d" % i, state_out.name))
            if state_rows:
                parts["delta_state"].append(fluid.layers.slice(
                    state_out, axes=[0], starts=[0], ends=[state_rows]))
            y = head_norm(o, value_heads, value_dim, block["out_norm"],
                          name="gdn_out_norm")
            return fluid.layers.elementwise_mul(
                y, fluid.layers.swish(z, name="gdn_out_norm"),
                name="gdn_out_norm")

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
            mixer = linear_mixer if layer_types[i] == LINEAR else full_mixer
            o = linear(mixer(i, h, block), d_model, block["wo"])
            parts["attn_out"].append(last(o))
            a = x + fluid.layers.cast(o, "float32")
            u = normed(a, block["pre_mlp_norm"])
            f, routing = share_feed_forward(
                u, block, False, 0, d_expert, n_experts, held, top_k,
                norm_topk, 1.0, scoring="softmax",
                shared_gate=block["shared_gate"])
            for key, value in routing.items():
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
