"""What `rope`, `moe_router`, `mla_cached_attention` and the latent step
builder lowered to before they grew their options (PR 38): the recipes,
and a recording of what the parent commit gave for them
(tests/data/parent_lowerings_pr37.json, written by running this file on
a checkout of commit c5d60e4: `python tests/parent_lowerings.py >
tests/data/parent_lowerings_pr37.json`).  tests/test_dsv32_program.py
runs the recipes on the tree as it is and holds them to the recording:
without their new inputs the ops lower as they did.

Since PR 53 the latent builder's all-slots step takes a block of
positions, so the builder called with pangu's arguments no longer builds
the recorded "program" op for op: the test holds the new Program to the
recording's products (every op that reads a parameter, with the
parameters it reads) in the recording's order.  What PR 53 promised to
leave alone has a recording of its own (`block_lowerings`,
tests/data/parent_lowerings_pr52.json, written by `python
tests/parent_lowerings.py blocks > tests/data/parent_lowerings_pr52.json`
on a checkout of commit b6c67fc, PR 52's): the latent builder with an
`indexer` (DeepSeek-V3.2's step, one position a call), the window/full
and the linear/full builders, op for op, and the jaxpr of a generation
call with a prompt of the three kinds of step that prefill in blocks of
`models.decode.PREFILL_BLOCK` (GPT-2's, the window/full one, the
linear/full one), a remainder block and one block of 128.

Since PR 62 a chooser's step takes a block too, so "program indexer" of
that recording is held to its products as pangu's is, and what PR 62
promised to leave alone has a recording of its own (`step_lowerings`,
tests/data/parent_lowerings_pr61.json, written by `python
tests/parent_lowerings.py steps > tests/data/parent_lowerings_pr61.json`
on a checkout of commit cc05484, PR 61's): the jaxprs of
`mla_index_select` and of `mla_cached_attention` over a chosen set, with
a sink and without, at one position a row.

Since PR 66 `cached_attention` takes a chosen set a position of a block,
and what PR 66 promised to leave alone has a recording of its own
(`attend_lowerings`, tests/data/parent_lowerings_pr65.json, written by
`python tests/parent_lowerings.py attends >
tests/data/parent_lowerings_pr65.json` on a checkout of commit 56cbe1e,
PR 65's): the jaxprs of `cached_attention` at one position a row over a
chosen set (128-wide heads: `gqa_decode_chosen`'s body included; 64-wide:
the plain path) and without one (the walk of the live slots over either
width, a ring, a cache the op only reads), and of a block of positions
without a chosen set.
"""

import json
import os
import sys

_DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RECORDING = os.path.join(_DATA, "parent_lowerings_pr37.json")
BLOCK_RECORDING = os.path.join(_DATA, "parent_lowerings_pr52.json")
STEP_RECORDING = os.path.join(_DATA, "parent_lowerings_pr61.json")
ATTEND_RECORDING = os.path.join(_DATA, "parent_lowerings_pr65.json")


def program_text(main):
    """A Program's ops, a line each: type, inputs, outputs, attrs."""
    return "\n".join(
        "%s(%s) -> %s %s" % (
            od.type,
            ", ".join("%s=%s" % (s, od.input(s))
                      for s in sorted(od.input_names())),
            ", ".join("%s=%s" % (s, od.output(s))
                      for s in sorted(od.output_names())),
            sorted(od.attrs.items()))
        for od in main.global_block().desc.ops)


def lowerings():
    """{name: text}: jaxprs of the three ops on small seeded inputs, and
    the step Program's ops as the builder's defaults make it."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from paddle_tpu.models.latent_moe_program import (
        build_latent_moe_cached_step_program)
    from paddle_tpu.ops import registry

    rs = np.random.RandomState(0)

    def draw(*shape, dtype=jnp.float32):
        return jnp.asarray(rs.randn(*shape), dtype)

    def jaxpr(op, ins, attrs):
        kernel = registry.get_op_info(op).kernel
        return str(jax.make_jaxpr(lambda i: kernel(None, i, attrs))(ins))

    out = {}
    rope_ins = {"X": [draw(2, 3, 4 * 8)],
                "Positions": [jnp.arange(6).reshape(2, 3)]}
    out["rope"] = jaxpr("rope", rope_ins, {"num_heads": 4, "theta": 1e4})
    router_ins = {"X": [draw(10, 16)], "W": [draw(16, 8)]}
    out["moe_router softmax"] = jaxpr("moe_router", router_ins, {"top_k": 2})
    out["moe_router sigmoid"] = jaxpr(
        "moe_router", router_ins,
        {"top_k": 2, "scoring": "sigmoid", "norm_topk": True, "scale": 2.5})
    b, t, h, nope, rope, latent, dv = 2, 6, 4, 8, 4, 8, 8
    for dtype in (jnp.float32, jnp.bfloat16):
        mla_ins = {
            "QNope": [draw(b, 1, h * nope, dtype=dtype)],
            "QRope": [draw(b, 1, h * rope, dtype=dtype)],
            "CNew": [draw(b, 1, latent, dtype=dtype)],
            "RNew": [draw(b, 1, rope, dtype=dtype)],
            "Cache": [draw(b, t, latent + rope, dtype=dtype)],
            "WUk": [draw(latent, h * nope, dtype=dtype)],
            "WUv": [draw(latent, h * dv, dtype=dtype)],
            "Position": [jnp.full((b,), 3, jnp.int32)]}
        out["mla_cached_attention %s" % jnp.dtype(dtype).name] = jaxpr(
            "mla_cached_attention", mla_ins, {"num_heads": h})
    main = build_latent_moe_cached_step_program(
        3, 12, 97, n_layer=3, n_dense=1, held=(2, 4))[0]
    out["program"] = program_text(main)
    return out


def block_lowerings():
    """{name: text}: the step Programs PR 53 leaves as they are, and the
    jaxpr of a greedy call with a prompt of 131 positions (a remainder
    of 3, then a block of 128) and two more tokens through each kind of
    step that prefills in blocks of 128, at the builders' tiny defaults
    on seeded weights."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    import paddle_tpu.fluid as fluid
    from paddle_tpu.models.decode import greedy_decode
    from paddle_tpu.models.latent_moe_program import (
        build_latent_moe_cached_step_program)
    from paddle_tpu.models.linear_moe_program import (
        build_linear_moe_cached_step_program)
    from paddle_tpu.models.transformer_program import (
        build_transformer_cached_step_program)
    from paddle_tpu.models.window_moe_program import (
        build_window_moe_cached_step_program)

    batch, extent, vocab, prompt_len = 2, 160, 97, 131
    out = {"program indexer": program_text(
        build_latent_moe_cached_step_program(
            2, 48, vocab, n_layer=2, n_dense=1, held=(2, 4), eps=1e-6,
            sandwich_norm=False, indexer=(8, 16, 8), n_group=4,
            topk_group=2, router_bias=True,
            yarn={"factor": 40, "original_positions": 16, "beta_fast": 32,
                  "beta_slow": 1, "mscale": 1})[0])}

    def call_text(built):
        """The jaxpr of prefill and two steps through a decoder over the
        step Program `built` = (main, startup, logits, state_pairs)."""
        main, startup, logits, pairs = built[:4]
        scope = fluid.Scope()
        startup.random_seed = 3
        fluid.Executor(fluid.CPUPlace()).run(startup, scope=scope)
        decoder = fluid.ProgramDecoder(
            main.clone(for_test=True), token_name="tok",
            logits_name=logits.name, state_pairs=pairs, scope=scope,
            max_positions=extent)
        block = main.global_block()
        state = {feed: jnp.zeros([n if n > 0 else batch
                                  for n in block.var(feed).shape],
                                 block.var(feed).dtype.replace("64", "32"))
                 for feed, _ in pairs}

        def call(params, state, prompt):
            return decoder._prefilled_run(
                params, state, prompt,
                lambda step, st, first: greedy_decode(
                    step, st, bos=first, eos=vocab, max_len=2,
                    batch_size=batch, with_state=True)[::2], vocab, 3)

        prompt = np.random.RandomState(1).randint(
            0, vocab, (batch, prompt_len)).astype("int32")
        return str(jax.make_jaxpr(call)(decoder._params, state, prompt))

    for name, built in (
            ("gpt2", build_transformer_cached_step_program(
                batch, extent, vocab)),
            ("window_moe", build_window_moe_cached_step_program(
                batch, extent, vocab, held=(2, 4))),
            ("linear_moe", build_linear_moe_cached_step_program(
                batch, extent, vocab, held=(2, 4)))):
        if name != "gpt2":
            out["program %s" % name] = program_text(built[0])
        out["call %s" % name] = call_text(built)
    return out


def step_lowerings():
    """{name: text}: the jaxprs of a chooser's two ops at one position a
    row (T = 1), on small seeded inputs in float32 and bfloat16: the
    chooser, the attention over its set, and that with a sink."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import registry

    rs = np.random.RandomState(0)
    b, t, h, nope, rope, latent, dv, ih, idim, top_k = \
        2, 24, 4, 8, 4, 8, 8, 4, 8, 6

    def jaxpr(op, ins, attrs):
        kernel = registry.get_op_info(op).kernel
        return str(jax.make_jaxpr(lambda i: kernel(None, i, attrs))(ins))

    out = {}
    for dtype in (jnp.float32, jnp.bfloat16):
        def draw(*shape):
            return jnp.asarray(rs.randn(*shape), dtype)

        name = jnp.dtype(dtype).name
        position = [jnp.full((b,), 9, jnp.int32)]
        out["mla_index_select %s" % name] = jaxpr(
            "mla_index_select",
            {"Q": [draw(b, 1, ih * idim)], "W": [draw(b, 1, ih)],
             "KNew": [draw(b, 1, idim)], "Cache": [draw(b, t, idim)],
             "Position": position},
            {"num_heads": ih, "top_k": top_k, "scale": 0.25})
        chosen = {
            "QNope": [draw(b, 1, h * nope)], "QRope": [draw(b, 1, h * rope)],
            "CNew": [draw(b, 1, latent)], "RNew": [draw(b, 1, rope)],
            "Cache": [draw(b, t, latent + rope)],
            "WUk": [draw(latent, h * nope)], "WUv": [draw(latent, h * dv)],
            "Position": position,
            "Selected": [jnp.asarray(
                np.sort(rs.rand(b, t).argsort(-1)[:, :top_k], -1),
                jnp.int32)],
            "Live": [jnp.full((b,), top_k, jnp.int32)]}
        out["mla_cached_attention chosen %s" % name] = jaxpr(
            "mla_cached_attention", chosen, {"num_heads": h})
        out["mla_cached_attention chosen sink %s" % name] = jaxpr(
            "mla_cached_attention",
            dict(chosen, Sink=[jnp.asarray(rs.randn(h), jnp.float32)]),
            {"num_heads": h, "sm_scale": 0.3})
    return out


def attend_lowerings():
    """{name: text}: the jaxprs of `cached_attention` on small seeded
    inputs in float32 and bfloat16, 2 rows of 4 query heads over 2
    key/value heads, and over 4 at 64 wide ("ungrouped": GPT-2's, whose
    step's kernel writes the slot too): a step (T = 1) over a chosen set
    and over every live slot, 128-wide heads (the kernels, their bodies
    in the text) and 64-wide (a chosen set: the plain path); a step
    through a ring and one that reads a cache it does not write; a block
    of 8 positions over every live slot."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import registry

    rs = np.random.RandomState(0)
    rows, heads, slots, top_k = 2, 4, 256, 128
    kernel = registry.get_op_info("cached_attention").kernel

    out = {}
    for dtype in (jnp.float32, jnp.bfloat16):
        for dim, kv_heads in ((64, 2), (64, 4), (128, 2)):
            def jaxpr(ins, **attrs):
                attrs = dict({"num_heads": heads, "num_kv_heads": kv_heads},
                             **attrs)
                return str(jax.make_jaxpr(
                    lambda i: kernel(None, i, attrs))(ins))

            def draw(*shape):
                return jnp.asarray(rs.randn(*shape), dtype)

            def ins(block, **more):
                return dict({
                    "Q": [draw(rows, block, heads * dim)],
                    "KNew": [draw(rows, block, kv_heads * dim)],
                    "VNew": [draw(rows, block, kv_heads * dim)],
                    "KCache": [draw(rows, kv_heads, slots, dim)],
                    "VCache": [draw(rows, kv_heads, slots, dim)],
                    "Position": [jnp.full((rows,), 200, jnp.int32)]}, **more)

            name = "%d-wide %s%s" % (
                dim, "ungrouped " if kv_heads == heads else "",
                jnp.dtype(dtype).name)
            out["step chosen " + name] = jaxpr(ins(
                1, Selected=[jnp.asarray(
                    np.sort(rs.rand(rows, 201).argsort(-1)[:, :top_k], -1),
                    jnp.int32)],
                Live=[jnp.full((rows,), top_k, jnp.int32)]))
            out["step " + name] = jaxpr(ins(1))
            out["step ring " + name] = jaxpr(ins(1), window=slots)
            reads = ins(1)
            del reads["KNew"], reads["VNew"]
            out["step read-only " + name] = jaxpr(reads)
            out["block " + name] = jaxpr(ins(8))
    return out


if __name__ == "__main__":
    sys.path.insert(0, os.getcwd())
    json.dump({"blocks": block_lowerings, "steps": step_lowerings,
               "attends": attend_lowerings}.get(
        "".join(sys.argv[1:]), lowerings)(), sys.stdout, indent=1,
        sort_keys=True)
