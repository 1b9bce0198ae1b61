"""Device milliseconds a step spends in the causal depthwise
convolution and its gradient (`causal_conv1d`, `causal_conv1d_grad`:
the operations under those ops' scopes): the memory-bound part of a
state-space layer, a few FLOPs a byte over [batch, seq, channels].
Prints forward and backward apart and the share of the HBM peak the
same instructions reached (their own operands and results,
benchmark/flops/elementwise.py `instruction_bytes`, over the peak,
against their device time).  First device, traced window, over its
steps."""

LAYER = "state-space layer"
MOVES = "train_items_per_s"
UNIT = "ms"
SOURCE = "device_trace"


def read(run):
    times = run.lookup.module("layer_metrics", "ssm_ms_per_step")
    found = times.type_seconds(run)
    steps = run.facts.get("traced_steps")
    if not found or not steps:
        return None
    conv = {t: found[t] for t in times.CONV_OPS if t in found}
    if not conv:
        return None
    line = ", ".join("%s %.3f ms and %.1f operations a step"
                     % (t, s / steps * 1e3, calls / steps)
                     for t, (s, calls) in conv.items())
    if run.peaks is not None:
        moved = run.lookup.module(
            "layer_metrics", "norm_rope_roofline").moved(
                run, times.CONV_OPS)
        if moved and moved[1]:
            nbytes, seconds, _ = moved
            peak = run.peaks["hbm_bytes_per_s"]
            line += ("; moved %.3f GB a step in %.3f ms, %.3f ms at the "
                     "HBM peak: %.1f%% of the HBM roofline"
                     % (nbytes / steps / 1e9, seconds / steps * 1e3,
                        nbytes / steps / peak * 1e3,
                        100.0 * nbytes / peak / seconds))
    print("convolution: %s" % line, flush=True)
    return sum(s for s, _ in conv.values()) / steps * 1e3
