"""Device time of a traced generation call of the long-session cell
(benchmark/drivers/decode_long.py) by the Program's op, inside the
call's decoding scan: benchmark/reduce/share_ops.py's reduction (read it
for how a path names its op type, instance and inner scopes), for a run
that carries the long-session driver's facts and no other's.  None for a
run without a chip, a trace, or those facts: the parent commit's, whose
program cannot build the cell, never gets here.
"""

import functools

from benchmark.reduce import op_instances, share_ops, xplane

parts = share_ops.parts
call_scans = share_ops.call_scans


def operations(run):
    """(the first device's operations with their paths, the instance
    sigil) of a traced run of the long-session driver on a chip, or
    None."""
    trace, mark = run.reduced, op_instances.sigil()
    if run.peaks is None or trace is None or not trace.devices \
            or mark is None or "long_step_applications" not in run.facts:
        return None
    return share_ops._operations(run.trace_dir, min(trace.devices),
                                 trace.window), mark


def decoding_steps(run):
    """(the decoding scan's interval, its steps), or None."""
    found = call_scans(run)
    steps = run.facts.get("long_gen_len", 0) - 1
    if found is None or steps < 1:
        return None
    return found[1], steps


def step_seconds(run, key):
    """{key(op type, instance, inner scopes): seconds a decoding step}
    of the operations under an op instance inside the traced call's
    decoding scan; those for which `key` gives None are left out.  None
    where there is nothing to read."""
    found, scan = operations(run), decoding_steps(run)
    if found is None or scan is None:
        return None
    (scoped, mark), ((lo, hi), steps) = found, scan
    out = {}
    for start, end, _, path in scoped.ops:
        where = parts(path, mark)
        name = key(*where) if where is not None else None
        if name is None:
            continue
        for s, e in xplane.clip([(start, end)], lo, hi):
            out[name] = out.get(name, 0.0) + (e - s) / steps
    return out


def mean_decode_position(run):
    """The mean slot the call's decoding steps write: they write slots
    session + prompt .. session + prompt + gen - 2."""
    facts = run.facts
    first = facts["long_session_len"] + facts["long_prompt_len"]
    return first + (facts["long_gen_len"] - 2) / 2.0


@functools.lru_cache(maxsize=1)
def _step_ops(run):
    """The op descs of the cell's step Program, built once more to name
    its instances (once a run: three readers ask)."""
    program = run.lookup.module("models", run.workload["builder"]).build(
        run.config, run.workload["batch"])["main"]
    return list(program.global_block().desc.ops)


def instances(run, op_type, wanted):
    """The instances of the `op_type` ops of the cell's step Program for
    which `wanted(op desc)` holds."""
    from paddle_tpu.fluid import executor

    return {executor.op_instance(od) for od in _step_ops(run)
            if od.type == op_type and wanted(od)}
