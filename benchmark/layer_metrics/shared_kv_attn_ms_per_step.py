"""Device milliseconds a decoding step of the traced generation call
spends attending the one whole-extent cache: `attn_full` (the layer
that writes it) and `attn_cross` (the layers that only read it) under
the `cached_attention` op, everything between the cache and the op's
output (on the kernel path the walk of the live slots,
`gqa_decode_k<block>`), and those layers' `diff_combine`.  The full
layer's `kv_write` is printed and not counted: it moves one slot.  First
device, inside the call's decoding scan, over its `gen_len - 1` steps.
Prints the time by layer kind and scope: those counted add up to the
value."""

from benchmark.reduce import yoco_ops

LAYER = "ops"
MOVES = "decode_tok_per_s"
UNIT = "ms"
SOURCE = "device_trace"
SCOPES = {"full": "attn_full", "cross": "attn_cross"}


def by_scope(run, kinds=("full", "cross")):
    """{(layer kind, scope): seconds a decoding step} of the attention
    ops of `kinds` and their `diff_combine`, or None."""
    if yoco_ops.operations(run) is None:
        return None
    instances = yoco_ops.attention_instances(run)

    def scope(op_type, instance, inner):
        for kind in kinds:
            attend, combine = instances[kind]
            if op_type == "cached_attention" and instance in attend:
                named = [p for p in inner if p in ("kv_write", "attn_" + kind)]
                return kind, named[0] if named else "(no scope)"
            if op_type == "diff_combine" and instance in combine:
                return kind, "diff_combine"
        return None

    return yoco_ops.step_seconds(run, scope)


def counted(found):
    return sum(s for (_, scope), s in found.items() if scope != "kv_write")


def read(run):
    found = by_scope(run)
    if not found:
        return None
    print("the shared cache's readers, device ms a decoding step by layer "
          "kind and scope: %s"
          % ", ".join("%s %s %.4f" % (kind, name, s * 1e3)
                      for (kind, name), s in sorted(found.items())),
          flush=True)
    return counted(found) * 1e3
