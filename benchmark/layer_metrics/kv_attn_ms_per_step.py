"""Device milliseconds a decoding step of the traced generation call
spends under the `cached_attention` op, every layer, window and full:
`kv_write` (the new key and value written into the ring or the extent),
`attn_window` and `attn_full` (everything between the caches and the
op's output: on the kernel path the walk of the live slots,
`gqa_decode_w<window>` and `gqa_decode_k<block>`).  First device, inside
the call's decoding scan, over its `gen_len - 1` steps.  Prints the time
by layer kind and scope: they add up to the value."""

from benchmark.reduce import long_ops

LAYER = "ops"
MOVES = "decode_tok_per_s"
UNIT = "ms"
SOURCE = "device_trace"
OP_TYPE = "cached_attention"
SCOPES = ("kv_write", "attn_window", "attn_full")


def read(run):
    if long_ops.operations(run) is None:
        return None
    rings = long_ops.instances(run, OP_TYPE,
                               lambda od: od.attrs.get("window", 0))

    def scope(kind, instance, inner):
        if kind != OP_TYPE:
            return None
        named = [p for p in inner if p in SCOPES]
        return ("window" if instance in rings else "full",
                named[0] if named else "(no scope)")

    found = long_ops.step_seconds(run, scope)
    if not found:
        return None
    print("%s, device ms a decoding step by layer kind and scope: %s"
          % (OP_TYPE, ", ".join("%s %s %.4f" % (kind, name, s * 1e3)
                                for (kind, name), s in sorted(found.items()))),
          flush=True)
    return sum(found.values()) * 1e3
