"""The share of a decode step's attention layers that chose nothing and
attended an inherited set, in percent: the `mla_cached_attention` op
instances with device time inside the traced call's decoding scan, less
the `mla_index_select` instances with time under `dsa_select` there, over
the former.  Read off the trace, not off the configuration: 60 where
three layers of five inherit their set, 0 the day a "shared" layer
chooses for itself (and 0 for a step whose every layer chooses)."""

from benchmark.reduce import reuse_ops

LAYER = "ops"
MOVES = "decode_tok_per_s"
UNIT = "%"
SOURCE = "device_trace"


def which(kind, instance, inner):
    if kind == "mla_cached_attention":
        return "attends"
    if kind == "mla_index_select" and "dsa_select" in inner:
        return "chooses"
    return None


def read(run):
    found = reuse_ops.step_instances(run, which)
    if not found or not found.get("attends"):
        return None
    attends, chooses = (len(found.get(k, ())) for k in ("attends", "chooses"))
    print("of %d attention layers in a decoding step, %d chose their own "
          "set" % (attends, chooses), flush=True)
    return 100.0 * (attends - chooses) / attends
