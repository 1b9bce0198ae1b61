"""What `rope`, `moe_router`, `mla_cached_attention` and the latent step
builder lowered to before they grew their options (PR 38): the recipes,
and a recording of what the parent commit gave for them
(tests/data/parent_lowerings_pr37.json, written by running this file on
a checkout of commit c5d60e4: `python tests/parent_lowerings.py >
tests/data/parent_lowerings_pr37.json`).  tests/test_dsv32_program.py
runs the recipes on the tree as it is and holds them to the recording:
without their new inputs the ops lower as they did, and the builder
called with pangu's arguments builds the Program it built.
"""

import json
import os
import sys

RECORDING = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                         "parent_lowerings_pr37.json")


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
    out["program"] = "\n".join(
        "%s(%s) -> %s %s" % (
            od.type,
            ", ".join("%s=%s" % (s, od.input(s))
                      for s in sorted(od.input_names())),
            ", ".join("%s=%s" % (s, od.output(s))
                      for s in sorted(od.output_names())),
            sorted(od.attrs.items()))
        for od in main.global_block().desc.ops)
    return out


if __name__ == "__main__":
    sys.path.insert(0, os.getcwd())
    json.dump(lowerings(), sys.stdout, indent=1, sort_keys=True)
