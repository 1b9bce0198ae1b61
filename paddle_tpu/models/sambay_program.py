"""A decoder-hybrid-decoder (SambaY, arXiv:2507.06607) as a cached
decode step Program: Phi-4-mini-flash-reasoning's model
(huggingface.co/microsoft/Phi-4-mini-flash-reasoning, `model_type`
`phi4flash`), whole.

A block of T >= 1 consecutive tokens of every row in (T = 1: a decode
step; a prompt's prefill feeds many), the logits after the block's last
out.  Layer i of `n_layer` (a multiple of four), `half` = n_layer / 2,
is (`layer_kinds`)

    i even, i <= half       Mamba-1: in_proj, `causal_conv1d` with its
                            tail, `selective_scan` with its state, a
                            gate, out_proj; layer `half` also hands on
                            its scan's output before the gate, the
                            **memory** `m` [batch, T, d_inner]
    i odd,  i <  half       differential attention over a ring of
                            `window` slots
    i = half + 1            differential attention over the whole extent:
                            the one full cache of the step
    i even, i >  half       a gated memory unit: W_out (silu(W_in h) * m)
    i odd,  i >  half + 1   differential *cross* attention: queries of
                            its own, the keys and values of layer half +
                            1, read through `cached_attention` without
                            KNew / VNew from that layer's KCacheOut /
                            VCacheOut Variables of the same step

between two LayerNorms with bias (pre-norm: u = x + mixer(LN1 x), x' = u
+ F(LN2 u), F the gated-SiLU feed-forward with gate and up in one
matrix), a last LayerNorm and the head tied to the embedding.  No rotary
or learned position anywhere.  The layers up to half + 1 are the
**self-decoder**: every state of the step is theirs ("ssm_state_<i>"
[batch, d_state, d_inner] float32 and "conv_tail_<i>" [batch, d_conv - 1,
d_inner]; "k_ring_<i>", "v_ring_<i>" [batch, kv pairs, window, 2 *
d_head]; "k_cache_<half+1>", "v_cache_<half+1>" [batch, kv pairs,
max_len, 2 * d_head]).  The layers past it, the **cross-decoder**, hold
none, so a block's logits need them at the block's last position alone:
after layer half + 1 the stream and the memory keep their last position
(`decoder_block.last`) and the cross-decoder runs one position whatever
T.  That is exact (no state is skipped) and is what makes the model's
prefill linear in the prompt.

Differential attention (arXiv:2410.05258) pairs the heads: pair p is
query heads 2p, 2p + 1 and reads key/value pair p // group; each head
of the pair has a softmax of its own over `d_head`-wide scores, both are
applied to the pair's values side by side (2 * d_head wide), and the
second is subtracted under a learned scalar before an RMSNorm over the
pair (`diff_combine`).  Here a pair's two key heads lie side by side as
one 2 * d_head-wide head of the cache, as the fused projection leaves
them, and a query is zero in the half it does not use, so both products
are `cached_attention`'s grouped-query attention over `n_head` queries
and `n_kv_head / 2` heads at `sm_scale` d_head ** -0.5 (a zero half adds
nothing to a score): at d_head 64 the cache's heads are 128 wide, which
`kernels/gqa_decode.py` walks for steps, rings and blocks, and no lane
of the cache is padding.

The residual stream is float32 whatever the weights' type, as the other
cached steps' (`window_moe_program.py` says why); the scan's step size,
B and C are projected in float32 (`decoder_block.linear_float32`: the
step of every decay would otherwise be rounded to eight bits), and the
scan's state is float32.  `fluid.ProgramDecoder` scans the step; the
token feed is declared [batch, -1].

The equations are in `models/reference/phi4_flash.py`, which the tests
hold this to.
"""

import math

import numpy as np

from .. import fluid
from ..fluid.param_attr import ParamAttr
from ..obs import telemetry
from .decoder_block import (block_positions, gated_feed_forward, last,
                            linear, linear_float32)

__all__ = ["build_sambay_cached_step_program", "sambay_param_names",
           "layer_kinds", "lambda_init", "MAMBA", "WINDOW", "FULL", "GMU",
           "CROSS"]

MAMBA, WINDOW, FULL, GMU, CROSS = "mamba", "window", "full", "gmu", "cross"
_NORMS = ("ln1.w", "ln1.b", "ln2.w", "ln2.b")
_FEED_FORWARD = ("ffn_in", "ffn_out")
_LAMBDAS = ("lq1", "lk1", "lq2", "lk2")
_MIXER = {
    MAMBA: ("in_proj", "conv_w", "conv_b", "x_proj", "dt_proj", "dt_bias",
            "a_log", "d", "out_proj"),
    WINDOW: ("wq", "wkv", "wo") + _LAMBDAS + ("subln",),
    FULL: ("wq", "wkv", "wo") + _LAMBDAS + ("subln",),
    CROSS: ("wq", "wo") + _LAMBDAS + ("subln",),
    GMU: ("gmu_in", "gmu_out"),
}


def layer_kinds(n_layer):
    """The mixer of every layer (the module's docstring)."""
    half = n_layer // 2
    if n_layer % 4 or n_layer < 4:
        raise ValueError("sambay: %d layers are no self-decoder and "
                         "cross-decoder of Mamba / attention pairs"
                         % n_layer)
    return tuple(
        (MAMBA if i <= half else GMU) if i % 2 == 0
        else WINDOW if i < half else FULL if i == half + 1 else CROSS
        for i in range(n_layer))


def lambda_init(layer):
    """The differential transformer's schedule of lambda's constant
    part by depth (arXiv:2410.05258, section 2.1)."""
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def sambay_param_names(n_layer):
    """The parameters' names, laid out as the reference's `params`."""
    def block(i, kind):
        return {w: "block_%d.%s" % (i, w)
                for w in _NORMS + _MIXER[kind] + _FEED_FORWARD}

    return {"embed": "embed.w",
            "blocks": [block(i, kind)
                       for i, kind in enumerate(layer_kinds(n_layer))],
            "norm_f": {"w": "norm_f.w", "b": "norm_f.b"}}


def build_sambay_cached_step_program(
        batch, max_len, vocab_size, n_layer=8, window=4, n_head=4,
        n_kv_head=2, d_head=16, d_model=64, d_ff=128, d_state=4, d_conv=4,
        expand=2, dt_rank=None, eps=1e-5, subtract=True,
        memory_after_gate=False, cross_before_write=False):
    """Returns (main, startup, logits, state_pairs, parts): feeds "tok"
    int32 [batch, T] (declared [batch, -1]: T >= 1 consecutive tokens of
    every row, read off the feed), "pos" int64 [batch], the position of
    the block's first token (rows move in lockstep), and the states the
    module's docstring names (declared float32; a feed is taken in the
    type it arrives in, and an op casts what it writes to its state's);
    `logits` [batch, vocab_size], of the block's last position alone;
    `state_pairs` wires every state and the position, advanced by T,
    into `fluid.ProgramDecoder` (pass max_positions=max_len).
    `dt_rank` defaults to ceil(d_model / 16).

    `parts` are **of the block's last position**, in shapes that T does
    not change, per layer: "mixer_in" [batch, 1, d_model], the mixer's
    normed input; "mixer_out" [batch, 1, d_model], what the mixer gave
    for it; and on a Mamba layer "scan_in" and "scan_out" [batch, 1,
    d_inner], the convolved input the scan read and its output before
    the gate (layer half's is the memory).

    The last three arguments build a step that is wrong on purpose, for
    the controls of a cell's `correct`: `subtract` False drops the
    second attention map, `memory_after_gate` hands on layer half's
    scan output after its gate, `cross_before_write` wires the cross
    layers to the full cache as it was fed, before the step's write."""
    kinds = layer_kinds(n_layer)
    half = n_layer // 2
    if n_head % 2 or n_kv_head % 2 or (n_head // 2) % (n_kv_head // 2):
        raise ValueError("sambay: %d query and %d key/value heads are no "
                         "pairs that group" % (n_head, n_kv_head))
    pairs, kv_pairs, width = n_head // 2, n_kv_head // 2, 2 * d_head
    d_inner = expand * d_model
    dt_rank = dt_rank or -(-d_model // 16)
    readers = kinds.count(CROSS) + 1
    names = sambay_param_names(n_layer)
    main = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(main, startup):
        def feed(name, shape, dtype="float32"):
            return fluid.layers.data(name=name, shape=shape, dtype=dtype,
                                     append_batch_size=False)

        tok = feed("tok", [batch, -1], "int32")
        pos = feed("pos", [batch], "int64")
        states = {}
        for i, kind in enumerate(kinds):
            if kind == MAMBA:
                states[i] = (
                    feed("ssm_state_%d" % i, [batch, d_state, d_inner]),
                    feed("conv_tail_%d" % i, [batch, d_conv - 1, d_inner]))
            elif kind in (WINDOW, FULL):
                stem = "%s_ring_%d" if kind == WINDOW else "%s_cache_%d"
                states[i] = tuple(
                    feed(stem % (which, i),
                         [batch, kv_pairs,
                          window if kind == WINDOW else max_len, width])
                    for which in "kv")
        embedded = fluid.layers.embedding(
            fluid.layers.reshape(x=fluid.layers.cast(tok, "int64"),
                                 shape=[0, 0, 1]),
            size=[vocab_size, d_model],
            param_attr=ParamAttr(name=names["embed"]))
        x = fluid.layers.cast(embedded, "float32")
        ones, _ = block_positions(tok, pos, batch)
        advance = fluid.layers.reduce_sum(ones)
        # the cross-decoder's one position: the block's last
        cross_pos = pos + advance - fluid.layers.fill_constant(
            shape=[1], dtype="int64", value=1)
        # a pair's two queries, each with the other's half zeroed
        halves = fluid.layers.cast(fluid.layers.assign(
            np.kron(np.eye(2), np.ones((1, d_head))).astype("float32"),
            fluid.layers.create_tensor("float32")), embedded)

        def normed(t, weight, bias):
            """LayerNorm of the float32 stream, in the weights' type."""
            return fluid.layers.cast(fluid.layers.layer_norm(
                t, begin_norm_axis=2, epsilon=eps,
                param_attr=ParamAttr(name=weight),
                bias_attr=ParamAttr(name=bias)), embedded)

        def mamba(i, block, h):
            """(the mixer's output, the scan's input and its output
            before the gate, what the layer hands on as memory)."""
            state, tail = states[i]
            xs, z = fluid.layers.split(
                linear(h, 2 * d_inner, block["in_proj"]), 2, dim=-1)
            xc, tail_out = fluid.layers.causal_conv1d(
                xs, d_conv, "silu", param_attr=ParamAttr(
                    name=block["conv_w"]),
                bias_attr=ParamAttr(name=block["conv_b"]), tail=tail)
            dt_low, b, c = fluid.layers.split(
                linear_float32(xc, dt_rank + 2 * d_state, block["x_proj"]),
                [dt_rank, d_state, d_state], dim=-1)
            y, state_out = fluid.layers.selective_scan(
                xc, linear_float32(dt_low, d_inner, block["dt_proj"]), b,
                c, state, d_state,
                a_log_attr=ParamAttr(name=block["a_log"]),
                d_attr=ParamAttr(name=block["d"]),
                dt_bias_attr=ParamAttr(name=block["dt_bias"]))
            state_pairs.append(("ssm_state_%d" % i, state_out.name))
            state_pairs.append(("conv_tail_%d" % i, tail_out.name))
            gated = y * fluid.layers.swish(z)
            return linear(gated, d_model, block["out_proj"]), xc, y, \
                gated if memory_after_gate else y

        def attention(i, block, h, kind, shared):
            q = fluid.layers.reshape(
                linear(h, n_head * d_head, block["wq"]),
                [0, 0, pairs, 1, width])
            q = fluid.layers.reshape(
                fluid.layers.expand(q, [1, 1, 1, 2, 1]) * halves,
                [0, 0, n_head * width])
            heads = dict(num_heads=n_head, num_kv_heads=kv_pairs,
                         sm_scale=d_head ** -0.5)
            if kind == CROSS:
                o = fluid.layers.cached_attention(
                    q, None, None, shared[0], shared[1], cross_pos,
                    reader=kinds[:i].count(CROSS) + 1, **heads)
            else:
                k, v = fluid.layers.split(
                    linear(h, 2 * n_kv_head * d_head, block["wkv"]), 2,
                    dim=-1)
                o, k_out, v_out = fluid.layers.cached_attention(
                    q, k, v, states[i][0], states[i][1], pos,
                    window=window if kind == WINDOW else 0,
                    shared_readers=readers if kind == FULL else 0, **heads)
                for feed_var, out in zip(states[i], (k_out, v_out)):
                    state_pairs.append((feed_var.name, out.name))
                if kind == FULL:
                    shared[:] = states[i] if cross_before_write \
                        else (k_out, v_out)
            a = fluid.layers.diff_combine(
                o, width, lambda_init(i), eps,
                lambda_attrs=[ParamAttr(name=block[w]) for w in _LAMBDAS],
                scale_attr=ParamAttr(name=block["subln"]),
                subtract=subtract)
            return linear(a, d_model, block["wo"])

        def memory_unit(i, block, h, m):
            # named: the ops' instances in a trace start with "gmu"
            name = "gmu_%d" % i
            g = fluid.layers.swish(fluid.layers.fc(
                input=h, size=d_inner, num_flatten_dims=2,
                param_attr=ParamAttr(name=block["gmu_in"]),
                bias_attr=False, name=name), name=name)
            return fluid.layers.fc(
                input=fluid.layers.elementwise_mul(g, m, name=name),
                size=d_model, num_flatten_dims=2,
                param_attr=ParamAttr(name=block["gmu_out"]),
                bias_attr=False, name=name)

        state_pairs = []
        parts = {"mixer_in": [], "mixer_out": [], "scan_in": [],
                 "scan_out": []}
        shared, memory = [], None
        for i, (kind, block) in enumerate(zip(kinds, names["blocks"])):
            h = normed(x, block["ln1.w"], block["ln1.b"])
            parts["mixer_in"].append(last(h))
            if kind == MAMBA:
                o, xc, y, handed = mamba(i, block, h)
                parts["scan_in"].append(last(xc))
                parts["scan_out"].append(last(y))
                if i == half:
                    memory = handed
            elif kind == GMU:
                o = memory_unit(i, block, h, memory)
            else:
                o = attention(i, block, h, kind, shared)
            parts["mixer_out"].append(last(o))
            u = x + fluid.layers.cast(o, "float32")
            f = gated_feed_forward(
                normed(u, block["ln2.w"], block["ln2.b"]), d_ff,
                {"w_in": block["ffn_in"], "w_out": block["ffn_out"]})
            x = u + fluid.layers.cast(f, "float32")
            if kind == FULL:
                # the cross-decoder holds no state: the block's last
                # position is all the logits need of it
                x, memory = last(x), last(memory)

        z = normed(x, names["norm_f"]["w"], names["norm_f"]["b"])
        logits = fluid.layers.reshape(
            x=fluid.layers.matmul(
                z, main.global_block().var(names["embed"]),
                transpose_y=True),
            shape=[batch, vocab_size])
        state_pairs.append(("pos", (pos + advance).name))
    telemetry.on_shared_cache_readers(main, readers)
    return main, startup, logits, state_pairs, parts
