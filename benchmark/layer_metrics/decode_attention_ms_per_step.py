"""Device milliseconds a step application of the traced generation call
spends under the `cached_attention` op's scopes: the cache's update, the
scores over the cache, the softmax and the weighted sum, every layer.
First device, traced call, over its `prompt_len + gen_len - 1` step
applications, prefill's among them.

`apply_op` opens the op type's scope and the instance's inside it under
the decoder's scan as under the executor, but the scan puts its own
scopes in front (`jit(<lambda>)/while/body/closed_call/layer_norm/
~layer_norm_3.tmp_0/mul`), so an operation's op type is read here as the
component before its instance's, not as the path's first
(benchmark/reduce/op_scopes.py `op_type`, which would say `while`).

Prints the ten op types with most time a step application and the time
of the operations under no op instance (the scan's own copies and
slices, the cache's entry copies): what `op_instance_named_share` is for
the training cells."""

from benchmark.reduce import op_instances, op_scopes, program_spans, xplane

LAYER = "ops"
MOVES = "decode_tok_per_s"
UNIT = "ms"
SOURCE = "device_trace"
OP_TYPE = "cached_attention"
SHOWN = 10


def type_under(path, mark):
    """The op type of a path: the scope that holds its instance's."""
    parts = op_scopes.components(path)
    for at in range(1, len(parts)):
        if parts[at].startswith(mark):
            return parts[at - 1]
    return None


def read(run):
    trace = run.reduced
    steps = run.facts.get("traced_step_applications")
    mark = op_instances.sigil()
    if run.peaks is None or trace is None or not trace.devices \
            or not steps or mark is None:
        return None
    ordinal = min(trace.devices)
    paths = op_scopes.metadata_stat(xplane.find_xplane(run.trace_dir),
                                    "/device:TPU:%d" % ordinal, "tf_op")
    found = op_scopes.scoped(program_spans.profile(run.trace_dir), paths,
                             ordinal, trace.window)
    by_type = dict(found.seconds(lambda path: type_under(path, mark)))
    unnamed = by_type.pop(None, [0.0, 0])
    if OP_TYPE not in by_type:
        return None
    total = unnamed[0] + sum(s for s, _ in by_type.values())
    print("device ms a step application by op type: %s; under no op "
          "instance %.3f (x%.0f), %.2f%% of the device's time"
          % (", ".join("%s %.3f (x%.0f)" % (kind, s / steps * 1e3,
                                             calls / steps)
                       for kind, (s, calls) in sorted(
                           by_type.items(),
                           key=lambda item: -item[1][0])[:SHOWN]),
             unnamed[0] / steps * 1e3, unnamed[1] / steps,
             100.0 * unnamed[0] / total), flush=True)
    return by_type[OP_TYPE][0] / steps * 1e3
