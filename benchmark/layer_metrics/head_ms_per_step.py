"""Device milliseconds a step under the output head's products: the
`mul` / `matmul` instances, forward and gradient, whose product is as
wide as the configuration's vocabulary (`vocab_size`): one on GPT-2 and
OLMoE, the tied one on granite, four on Ouro (a head after every pass).
Prints each and its share of the roofline.  The rows are
`matmul_roofline`'s; first device, traced window, over its steps."""

LAYER = "ops"
MOVES = "train_items_per_s"
UNIT = "ms"
SOURCE = "device_trace"


def read(run):
    reader = run.lookup.module("layer_metrics", "matmul_roofline")
    rows = reader.rows(run)
    if not rows:
        return None
    heads = [r for r in rows
             if r["shape"][2] == run.config[reader.VOCABULARY]]
    if not heads:
        return None
    print("head products: %s" % ", ".join(
        "%s %dx%dx%d forward %.3f backward %.3f ms (%.1f%% of the roofline)"
        % ((r["instance"],) + r["shape"]
           + (r["forward"] * 1e3, r["backward"] * 1e3,
              100.0 * r["floor"] / (r["forward"] + r["backward"])
              if r["forward"] + r["backward"] else 0.0))
        for r in heads), flush=True)
    return sum(r["forward"] + r["backward"] for r in heads) * 1e3
