"""Device milliseconds a decoding step of the traced generation call
spends under the `cached_attention` ops of the *window* layers (those
whose op carries a `window`: a ring of that many slots): the ring's
write and the attention over it, whatever the session's length.  The
part of `kv_attn_ms_per_step` that does not grow with the session.
First device, inside the call's decoding scan, over its `gen_len - 1`
steps."""

from benchmark.reduce import long_ops

LAYER = "ops"
MOVES = "decode_tok_per_s"
UNIT = "ms"
SOURCE = "device_trace"
OP_TYPE = "cached_attention"


def read(run):
    if long_ops.operations(run) is None:
        return None
    rings = long_ops.instances(run, OP_TYPE,
                               lambda od: od.attrs.get("window", 0))
    found = long_ops.step_seconds(
        run, lambda kind, instance, inner:
        (kind == OP_TYPE and instance in rings) or None)
    if not found or not rings:
        return None
    print("%s over a ring: %d layers, %.4f ms a decoding step on the "
          "device, %.4f a layer" % (OP_TYPE, len(rings), found[True] * 1e3,
                                    found[True] * 1e3 / len(rings)),
          flush=True)
    return found[True] * 1e3
