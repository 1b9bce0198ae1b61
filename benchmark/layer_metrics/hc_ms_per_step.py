"""Device milliseconds a decoding step of the traced generation call
spends mixing the residual's streams: everything under the scope
`hyper_connection` or under one of its three ops (a fusion whose root is
the cast back to the streams' type carries the op's name and not the
scope's: ops/hyper_connection.py), which the ops `hc_maps`
(the RMSNorm over the streams, the float32 product with the projections,
two sigmoids, `exp` and the Sinkhorn iterations), `hc_pre` (a sub-layer's
input read off the streams) and `hc_post` (the streams mixed and the
sub-layer's output written back) open, two applications a layer.  First
device, inside the call's decoding scan, over its `gen_len - 1` steps.
Prints the three ops apart: they add up to the value.  What a fused form
of the mappings would take away (ROADMAP A8(a))."""

from benchmark.reduce import reuse_ops

LAYER = "ops"
MOVES = "decode_tok_per_s"
UNIT = "ms"
SOURCE = "device_trace"
SCOPE = "hyper_connection"
OP_TYPES = ("hc_maps", "hc_pre", "hc_post")


def read(run):
    found = reuse_ops.step_seconds(
        run, lambda kind, inst, inner:
        kind if SCOPE in inner or kind in OP_TYPES else None)
    if not found:
        return None
    print("hyper-connections, device ms a decoding step by op: %s"
          % ", ".join("%s %.4f" % (name, s * 1e3)
                      for name, s in sorted(found.items())), flush=True)
    return sum(found.values()) * 1e3
