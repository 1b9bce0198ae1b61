"""Device milliseconds a decoding step of the traced generation call
spends under the two ops of sparse attention over grouped key/value
caches, every layer: the chooser `mla_index_select` (`dsa_index`: the
key's write and the index scores, the heads' products, relu, their
weighted sum and the mask past the position; `dsa_select`: the top-k)
and `cached_attention` over the chosen set (`kv_write`: the step's slot
into both caches; `kv_gather`: the chosen keys and values copied out of
them; `attn_sparse`: the group's queries over the gathered slots).
First device, inside the call's decoding scan, over its `gen_len - 1`
steps.  Prints the scopes apart: they add up to the value.  What
`dsa_ms_per_step` is for the sparse latent cell."""

from benchmark.reduce import sparse_ops

LAYER = "ops"
MOVES = "decode_tok_per_s"
UNIT = "ms"
SOURCE = "device_trace"
OP_TYPES = ("mla_index_select", "cached_attention")
PHASES = ("dsa_index", "dsa_select", "kv_write", "kv_gather", "attn_sparse")


def phase(kind, instance, inner):
    if kind not in OP_TYPES:
        return None
    named = [p for p in inner if p in PHASES]
    return named[0] if named else "%s (no scope)" % kind


def read(run):
    found = sparse_ops.step_seconds(run, phase)
    if not found:
        return None
    print("sparse key/value attention, device ms a decoding step by scope: "
          "%s" % ", ".join("%s %.4f" % (name, s * 1e3)
                           for name, s in sorted(found.items())), flush=True)
    return sum(found.values()) * 1e3
