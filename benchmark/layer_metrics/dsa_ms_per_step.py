"""Device milliseconds a decoding step of the traced generation call
spends under the two ops of sparse latent attention, every layer: the
chooser `mla_index_select` (`dsa_index`: the key's write and the index
scores, the heads' products, relu, their weighted sum and the mask past
the position; `dsa_select`: the top-k) and `mla_cached_attention` over the chosen set (`dsa_gather`: the
chosen latents copied out of the cache; `mla_absorb`, `mla_scores`,
`mla_values`; the latents' write under no scope of its own).  First
device, inside the call's decoding scan, over its `gen_len - 1` steps.
Prints the scopes apart: they add up to the value."""

from benchmark.reduce import session_ops

LAYER = "ops"
MOVES = "decode_tok_per_s"
UNIT = "ms"
SOURCE = "device_trace"
OP_TYPES = ("mla_index_select", "mla_cached_attention")
PHASES = ("dsa_index", "dsa_select", "dsa_gather", "mla_absorb",
          "mla_scores", "mla_values")


def phase(kind, instance, inner):
    if kind not in OP_TYPES:
        return None
    named = [p for p in inner if p in PHASES]
    return named[0] if named else "%s (no scope)" % kind


def read(run):
    found = session_ops.step_seconds(run, phase)
    if not found:
        return None
    print("sparse latent attention, device ms a decoding step by scope: %s"
          % ", ".join("%s %.4f" % (name, s * 1e3)
                      for name, s in sorted(found.items())), flush=True)
    return sum(found.values()) * 1e3
