"""Device milliseconds a step application of the traced generation call
spends under the `mla_cached_attention` op's scopes: the cache's update,
the absorbed query (`mla_absorb`), the scores over the cache and their
softmax (`mla_scores`), the weighted sum of latents and its
up-projection (`mla_values`), every layer.  First device, traced call,
over its `prompt_len + gen_len - 1` step applications, prefill's among
them.  Prints the op's scopes apart, and the ten op types with most
time."""

from benchmark.reduce import share_ops

LAYER = "ops"
MOVES = "decode_tok_per_s"
UNIT = "ms"
SOURCE = "device_trace"
OP_TYPE = "mla_cached_attention"
PHASES = ("mla_absorb", "mla_scores", "mla_values")
SHOWN = 10


def phase(kind, instance, inner):
    if kind != OP_TYPE:
        return None
    named = [p for p in inner if p in PHASES]
    return named[0] if named else "(no scope)"


def read(run):
    by_type = share_ops.seconds(run, lambda kind, inst, inner: kind)
    if not by_type or OP_TYPE not in by_type:
        return None
    steps = run.facts["share_step_applications"]
    print("device ms a step application by op type: %s" % ", ".join(
        "%s %.3f (x%.0f)" % (kind, s / steps * 1e3, calls / steps)
        for kind, (s, calls) in sorted(
            by_type.items(), key=lambda item: -item[1][0])[:SHOWN]),
        flush=True)
    print("%s by scope: %s" % (OP_TYPE, ", ".join(
        "%s %.3f ms (x%.0f)" % (name, s / steps * 1e3, calls / steps)
        for name, (s, calls) in sorted(
            share_ops.seconds(run, phase).items()))), flush=True)
    return by_type[OP_TYPE][0] / steps * 1e3
