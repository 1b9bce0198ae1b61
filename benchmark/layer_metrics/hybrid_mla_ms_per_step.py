"""Device milliseconds a decoding step of the traced generation call
spends in the latent-attention layers of the hybrid cell's share: the
`mla_cached_attention` op (`mla_absorb`, `mla_scores`: on the kernel
path the walk of the live slots, `mla_decode_k<block>`; `mla_values`;
the slot's write outside them), the head-wise output gate's elementwise
ops (the instances the builder names `latent_gate`), the rotations and
the mixer's projections (the `mul` ops that read a `wq_nope`, `wq_rope`,
`w_dkv`, `w_z` or a latent layer's `wo`).  First device, inside the
calls' scans of steps, a step.  Prints the parts apart, and which path
the op took."""

from benchmark.reduce import hybrid_ops

LAYER = "ops"
MOVES = "decode_tok_per_s"
UNIT = "ms"
SOURCE = "device_trace"
OP_TYPE = "mla_cached_attention"
SCOPES = ("mla_absorb", "mla_scores", "mla_values")
GATE = "latent_gate"
KERNEL = "mla_decode_k"
OWN = (".wq_nope", ".wq_rope", ".w_dkv", ".w_z")


def part_of(run):
    ops = hybrid_ops._step_ops(run)
    latent = {od.input("Y")[0].rsplit(".", 1)[0] for od in ops
              if od.type == "mul" and od.input("Y")[0].endswith(".w_dkv")}
    projections = hybrid_ops.instances(
        run, "mul", lambda od: od.input("Y")[0].endswith(OWN) or (
            od.input("Y")[0].endswith(".wo")
            and od.input("Y")[0].rsplit(".", 1)[0] in latent))

    def part(kind, instance, inner):
        if kind == OP_TYPE:
            named = [p for p in inner if p in SCOPES]
            return named[0] if named else "slot write (no scope)"
        if instance[1:].startswith(GATE):
            return GATE
        if kind == "rope":
            return "rope"
        return "projections" if kind == "mul" and instance in projections \
            else None
    return part


def read(run):
    if hybrid_ops.calls(run) is None:
        return None
    found = hybrid_ops.step_seconds(run, part_of(run))
    if not found or "mla_scores" not in found:
        return None
    kernel = hybrid_ops.kernel_step_seconds(run, KERNEL)
    print("latent attention, device ms a decoding step: %s; %s"
          % (", ".join("%s %.4f" % (name, s * 1e3)
                       for name, s in sorted(found.items())),
             "%s* %.4f ms (x%.1f)" % (KERNEL, kernel[0] * 1e3, kernel[1])
             if kernel[1] else "the plain path (no %s* kernel)" % KERNEL),
          flush=True)
    return sum(found.values()) * 1e3
