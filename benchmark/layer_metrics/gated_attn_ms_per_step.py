"""Device milliseconds a decoding step of the traced generation call
spends in the full layers' gated attention: the `cached_attention` op
(`kv_write`, `attn_full`: on the kernel path the walk of the live slots,
`gqa_decode_k<block>_d256`) and the output gate's elementwise ops (the
instances the builder names `attn_gate`).  First device, inside the
calls' scans of steps, a step.  Prints the parts apart, and which path
the op took."""

from benchmark.reduce import state_ops

LAYER = "ops"
MOVES = "decode_tok_per_s"
UNIT = "ms"
SOURCE = "device_trace"
OP_TYPE = "cached_attention"
SCOPES = ("kv_write", "attn_full")
GATE = "attn_gate"
KERNEL = "gqa_decode_k"


def part(kind, instance, inner):
    if kind == OP_TYPE:
        named = [p for p in inner if p in SCOPES]
        return named[0] if named else "(no scope)"
    return GATE if instance[1:].startswith(GATE) else None


def read(run):
    if state_ops.calls(run) is None:
        return None
    found = state_ops.step_seconds(run, part)
    if not found or "attn_full" not in found:
        return None
    kernel = state_ops.kernel_step_seconds(run, KERNEL)
    print("gated attention, device ms a decoding step: %s; %s"
          % (", ".join("%s %.4f" % (name, s * 1e3)
                       for name, s in sorted(found.items())),
             "%s* %.4f ms (x%.1f)" % (KERNEL, kernel[0] * 1e3, kernel[1])
             if kernel[1] else "the plain path (no %s* kernel)" % KERNEL),
          flush=True)
    return sum(found.values()) * 1e3
