"""Device time of a traced call of the block-diffusion cell
(benchmark/drivers/decode_diffusion.py) by what a pass spends it on:
decoder_trace.py's account of a call (the `decode/call` span, the
`decode_steps` and `decode_prefill` scopes) cut by the scopes
`models/decode.py block_diffusion_decode` opens inside `decode_steps`
(`diffusion_denoise`, `diffusion_unmask`, `diffusion_commit`) and by
share_ops.py's reading of a path (op type, instance, the scopes inside
it).  A call's passes are the driver's facts (what the call itself
returned), not `decoder_trace.steps_of`, which counts a step a token.
None for a run without a chip, a trace, the decoder's spans or the
driver's facts: a cell of another driver, or a program without the
scopes (the parent's), never gets further than the first line.
"""

import functools

from benchmark.reduce import decoder_trace, op_instances, op_scopes, \
    share_ops

DENOISE, UNMASK, COMMIT = ("diffusion_denoise", "diffusion_unmask",
                           "diffusion_commit")
ATTENTION = "cached_attention"
EXPERTS = ("moe_router", "moe_experts")


def passes(run):
    """(denoising passes, commit passes) a call, or None for a run of
    another driver."""
    facts = run.facts
    if "diffusion_denoise_passes" not in facts:
        return None
    return facts["diffusion_denoise_passes"], facts["diffusion_commit_passes"]


def calls(run):
    """([decoder_trace.Parts with a scan of steps], the instance sigil)
    of the traced calls, or None."""
    if passes(run) is None:
        return None
    mark = op_instances.sigil()
    found = [part for part in decoder_trace.parts(run) or ()
             if part.steps is not None]
    return (found, mark) if found and mark is not None else None


def pass_seconds(run):
    """Seconds of the first device's time a pass takes: the seconds an
    operation ran inside the traced calls' scans of blocks, over their
    passes, or None."""
    found = calls(run)
    if found is None:
        return None
    each = sum(passes(run))
    return sum(part.busy(part.steps) for part in found[0]) \
        / each / len(found[0])


@functools.lru_cache(maxsize=1)
def _head_instance(run):
    """The instance of the `mul` that reads the head."""
    from paddle_tpu.fluid import executor

    built = run.lookup.module("models", run.workload["builder"]).build(
        run.config, run.workload["batch"], run.workload["reference_rows"])
    head = built["param_names"]["head"]
    return {executor.op_instance(od)
            for od in built["main"].global_block().desc.ops
            if od.type == "mul" and od.input("Y")[0] == head}


def by_part(run):
    """{part: [seconds a pass, operations a pass]} of the work inside
    the traced calls' scans of blocks: "unmask" (under
    `diffusion_unmask`), "attention" (the `cached_attention` op: its
    write and its walk), "experts" (`moe_router`, `moe_experts`), "head"
    (the product with the head), "other ops" (the rest under an op
    instance: projections, norms, rotations, the embedding) and
    "unscoped" (under no op instance and not the rule's); over all of a
    call's passes, so that the parts add up to a pass.  None as
    `calls`."""
    found = calls(run)
    if found is None:
        return None
    parts, mark = found
    head = _head_instance(run)
    each = sum(passes(run)) * len(parts)
    out = {}
    for part in parts:
        for op in part.work(part.steps):
            where = share_ops.parts(op.path, mark)
            if UNMASK in op_scopes.components(op.path):
                name = "unmask"
            elif where is None:
                name = "unscoped"
            elif where[0] == ATTENTION:
                name = "attention"
            elif where[0] in EXPERTS:
                name = "experts"
            elif where[0] == "mul" and where[1] in head:
                name = "head"
            else:
                name = "other ops"
            entry = out.setdefault(name, [0.0, 0.0])
            entry[0] += (op.end - op.start) / each
            entry[1] += 1.0 / each
    return out


def kernel_pass_seconds(run, prefix, suffix=""):
    """(seconds, calls) a pass of the operations whose name starts with
    `prefix` and ends with `suffix` (before the compiler's own `.<n>`)
    inside the scans of blocks, or None."""
    found = calls(run)
    if found is None:
        return None
    each = sum(passes(run)) * len(found[0])
    named = [op for part in found[0] for op in part.work(part.steps)
             if op.name.startswith(prefix)
             and op.name.split(".")[0].endswith(suffix)]
    return sum(op.end - op.start for op in named) / each, len(named) / each


def report(run, part):
    """The milliseconds a pass of one of `by_part`'s parts, printed with
    the others beside it; None where there is nothing to read."""
    found = by_part(run)
    if not found or part not in found:
        return None
    print("%s, device ms a pass of %d (%d denoise, %d commit): %.4f over "
          "%.1f operations; beside it: %s"
          % ((part, sum(passes(run))) + passes(run)
             + (found[part][0] * 1e3, found[part][1],
                ", ".join("%s %.4f" % (name, s * 1e3)
                          for name, (s, _) in sorted(found.items())
                          if name != part))), flush=True)
    return found[part][0] * 1e3
