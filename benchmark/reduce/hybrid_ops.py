"""Device time of a traced generation call of the hybrid cell
(benchmark/drivers/decode_hybrid.py: the delta rule under a gate a key
channel beside latent attention) by the Program's op, inside the call's
scan of steps or inside its prefill: what state_ops.py is for the state
driver's run, for a run that carries the hybrid driver's facts and no
other's (that file's functions read `state_*` facts by name, so a copy
and not an import: folding the generation cells' five reduce files into
one that is told its driver's prefix is a `benchmark` PR's, PERF.md
section 7).  None for a run without a chip, a
trace, the decoder's spans or those facts: a cell of another driver, or
a program from before the decoder had spans, never gets further than the
first line.
"""

import functools

from benchmark.reduce import decoder_trace, op_instances, share_ops


def calls(run):
    """([decoder_trace.Parts with a scan of steps], the instance sigil)
    of the traced calls, or None."""
    if "hybrid_gen_len" not in run.facts:
        return None
    mark = op_instances.sigil()
    found = [part for part in decoder_trace.parts(run) or ()
             if part.steps is not None]
    return (found, mark) if found and mark is not None else None


def _seconds(run, key, interval_of, each):
    found = calls(run)
    if found is None:
        return None
    parts, mark = found
    out = {}
    for part in parts:
        interval = interval_of(part)
        if interval is None:
            continue
        for op in part.work(interval):
            where = share_ops.parts(op.path, mark)
            name = key(*where) if where is not None else None
            if name is not None:
                out[name] = out.get(name, 0.0) \
                    + (op.end - op.start) / each(part) / len(parts)
    return out


def step_seconds(run, key):
    """{key(op type, instance, inner scopes): seconds a decoding step}
    of the operations under an op instance inside the traced calls'
    scans of steps (the mean over the calls); those for which `key`
    gives None are left out."""
    return _seconds(run, key, lambda part: part.steps,
                    lambda part: decoder_trace.steps_of(part.call))


def prefill_seconds(run, key):
    """The same inside a call's prefill, seconds a call."""
    return _seconds(run, key, lambda part: part.prefill, lambda part: 1)


def kernel_step_seconds(run, prefix):
    """(seconds, calls) a decoding step of the operations whose name
    starts with `prefix` inside the scans of steps, or None."""
    found = calls(run)
    if found is None:
        return None
    seconds = count = 0.0
    for part in found[0]:
        steps = decoder_trace.steps_of(part.call)
        named = [op for op in part.work(part.steps)
                 if op.name.startswith(prefix)]
        seconds += sum(op.end - op.start for op in named) / steps
        count += len(named) / steps
    return seconds / len(found[0]), count / len(found[0])


def device_step_seconds(run):
    """Seconds of the first device's time a decoding step takes
    (`decode_device_step_ms`'s), or None."""
    found = calls(run)
    if found is None:
        return None
    return sum(part.busy(part.steps) / decoder_trace.steps_of(part.call)
               for part in found[0]) / len(found[0])


def mean_decode_position(run):
    """The mean slot the call's decoding steps write: slots prompt ..
    prompt + gen - 2."""
    facts = run.facts
    return facts["hybrid_prompt_len"] + (facts["hybrid_gen_len"] - 2) / 2.0


@functools.lru_cache(maxsize=1)
def _step_ops(run):
    """The op descs of the cell's step Program, built once more to name
    its instances (once a run)."""
    program = run.lookup.module("models", run.workload["builder"]).build(
        run.config, run.workload["batch"],
        run.workload["state_rows"])["main"]
    return list(program.global_block().desc.ops)


def instances(run, op_type, wanted):
    """The instances of the `op_type` ops of the cell's step Program for
    which `wanted(op desc)` holds."""
    from paddle_tpu.fluid import executor

    return {executor.op_instance(od) for od in _step_ops(run)
            if od.type == op_type and wanted(od)}
