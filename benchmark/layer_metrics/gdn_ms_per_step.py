"""Device milliseconds a decoding step of the traced generation call
spends in the linear-attention (Gated DeltaNet) layers' mixers: the
`gated_delta_rule` op (`gdn_gates`: the l2 norms; `gdn_state`: the step
kernel `gdn_step_*`, or the plain step), the `causal_conv1d` op with its
tail (`gdn_conv`), the gates' and the output norm's elementwise ops
(the instances the builder names `gdn_gates` and `gdn_out_norm`) and the
mixer's three projections (the `mul` ops that read a `w_qkvz`, `w_ba` or
a linear layer's `wo`).  First device, inside the calls' scans of steps,
a step.  Prints the parts apart: they add up to the value."""

from benchmark.reduce import state_ops

LAYER = "ops"
MOVES = "decode_tok_per_s"
UNIT = "ms"
SOURCE = "device_trace"
RULE, CONV = "gated_delta_rule", "causal_conv1d"
SCOPES = ("gdn_gates", "gdn_state", "gdn_chunks")
NAMED = ("gdn_gates", "gdn_out_norm")


def linear_projections(run):
    """The instances of the `mul` ops of the linear layers' mixers."""
    ops = state_ops._step_ops(run)
    linear = {od.input("Y")[0].rsplit(".", 1)[0] for od in ops
              if od.type == "mul" and od.input("Y")[0].endswith(".w_qkvz")}
    return state_ops.instances(
        run, "mul", lambda od: od.input("Y")[0].endswith(
            (".w_qkvz", ".w_ba")) or (
                od.input("Y")[0].endswith(".wo")
                and od.input("Y")[0].rsplit(".", 1)[0] in linear))


def part_of(run):
    projections = linear_projections(run)

    def part(kind, instance, inner):
        if kind == RULE:
            named = [p for p in inner if p in SCOPES]
            return named[0] if named else "gated_delta_rule (no scope)"
        if kind == CONV:
            return "gdn_conv"
        for name in NAMED:
            if instance[1:].startswith(name):
                return name + " (elementwise)"
        return "projections" if kind == "mul" and instance in projections \
            else None
    return part


def read(run):
    if state_ops.calls(run) is None:
        return None
    found = state_ops.step_seconds(run, part_of(run))
    if not found or "gdn_state" not in found:
        return None
    print("linear-attention mixers, device ms a decoding step: %s"
          % ", ".join("%s %.4f" % (name, s * 1e3)
                      for name, s in sorted(found.items())), flush=True)
    return sum(found.values()) * 1e3
