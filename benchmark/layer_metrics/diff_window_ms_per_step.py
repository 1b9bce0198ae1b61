"""Device milliseconds a decoding step of the traced generation call
spends attending the window layers' rings: `attn_window` under the
`cached_attention` op of every window layer (on the kernel path
`gqa_decode_w<window>`).  Their `kv_write` and `diff_combine` are
printed and not counted.  First device, inside the call's decoding scan,
over its `gen_len - 1` steps."""

LAYER = "ops"
MOVES = "decode_tok_per_s"
UNIT = "ms"
SOURCE = "device_trace"
SCOPE = "attn_window"


def read(run):
    found = run.lookup.module(
        "layer_metrics", "shared_kv_attn_ms_per_step").by_scope(
            run, ("window",))
    if not found:
        return None
    print("the window layers, device ms a decoding step by scope: %s"
          % ", ".join("%s %.4f" % (name, s * 1e3)
                      for (_, name), s in sorted(found.items())),
          flush=True)
    return found.get(("window", SCOPE), 0.0) * 1e3
