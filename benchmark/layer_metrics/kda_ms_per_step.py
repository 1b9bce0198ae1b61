"""Device milliseconds a decoding step of the traced generation call
spends in the KDA (Kimi Delta Attention) layers' mixers: the
`gated_delta_rule` op under a gate a key channel (`gdn_gates`: the l2
norms; `kda_state`: the step kernel `kda_step_*`, or the plain step),
the `causal_conv1d` op with its tail (`kda_conv`), the gates' and the
output norm's and head-wise gate's elementwise ops (the instances the
builder names `kda_gates` and `kda_out_norm`) and the mixer's three
projections (the `mul` ops that read a `w_qkvf`, `w_bz` or a KDA layer's
`wo`).  First device, inside the calls' scans of steps, a step.  Prints
the parts apart: they add up to the value.  Silent for a program
without `kda_state` (the parent's: the op refuses the gate's shape)."""

from benchmark.reduce import hybrid_ops

LAYER = "ops"
MOVES = "decode_tok_per_s"
UNIT = "ms"
SOURCE = "device_trace"
RULE, CONV = "gated_delta_rule", "causal_conv1d"
SCOPES = ("gdn_gates", "kda_state", "kda_chunks")
NAMED = ("kda_gates", "kda_out_norm")


def kda_projections(run):
    """The instances of the `mul` ops of the KDA layers' mixers."""
    ops = hybrid_ops._step_ops(run)
    kda = {od.input("Y")[0].rsplit(".", 1)[0] for od in ops
           if od.type == "mul" and od.input("Y")[0].endswith(".w_qkvf")}
    return hybrid_ops.instances(
        run, "mul", lambda od: od.input("Y")[0].endswith(
            (".w_qkvf", ".w_bz")) or (
                od.input("Y")[0].endswith(".wo")
                and od.input("Y")[0].rsplit(".", 1)[0] in kda))


def part_of(run):
    projections = kda_projections(run)

    def part(kind, instance, inner):
        if kind == RULE:
            named = [p for p in inner if p in SCOPES]
            return named[0] if named else "gated_delta_rule (no scope)"
        if kind == CONV:
            return "kda_conv"
        for name in NAMED:
            if instance[1:].startswith(name):
                return name + " (elementwise)"
        return "projections" if kind == "mul" and instance in projections \
            else None
    return part


def read(run):
    if hybrid_ops.calls(run) is None:
        return None
    found = hybrid_ops.step_seconds(run, part_of(run))
    if not found or "kda_state" not in found:
        return None
    print("KDA mixers, device ms a decoding step: %s"
          % ", ".join("%s %.4f" % (name, s * 1e3)
                      for name, s in sorted(found.items())), flush=True)
    return sum(found.values()) * 1e3
