"""Device time of a traced generation call of the reuse cell
(benchmark/drivers/decode_reuse.py) by the Program's op, inside the
call's decoding scan: benchmark/reduce/share_ops.py's reduction (read it
for how a path names its op type, instance and inner scopes), for a run
that carries the reuse driver's facts and no other driver's.  None for
a run without a chip, a trace, or those facts.
"""

from benchmark.reduce import op_instances, share_ops, xplane

parts = share_ops.parts
call_scans = share_ops.call_scans


def operations(run):
    """(the first device's operations with their paths, the instance
    sigil) of a traced run of the reuse driver on a chip, or None."""
    trace, mark = run.reduced, op_instances.sigil()
    if run.peaks is None or trace is None or not trace.devices \
            or mark is None or "reuse_step_applications" not in run.facts:
        return None
    return share_ops._operations(run.trace_dir, min(trace.devices),
                                 trace.window), mark


def decoding_steps(run):
    """(the decoding scan's interval, its steps), or None."""
    found = call_scans(run)
    steps = run.facts.get("reuse_gen_len", 0) - 1
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
    first = facts["reuse_session_len"] + facts["reuse_prompt_len"]
    return first + (facts["reuse_gen_len"] - 2) / 2.0


def step_instances(run, key):
    """{key(op type, instance, inner scopes): the op instances with an
    operation inside the traced call's decoding scan}; those for which
    `key` gives None are left out.  None where there is nothing to
    read."""
    found, scan = operations(run), decoding_steps(run)
    if found is None or scan is None:
        return None
    (scoped, mark), ((lo, hi), _) = found, scan
    out = {}
    for start, end, _, path in scoped.ops:
        where = parts(path, mark)
        name = key(*where) if where is not None else None
        if name is not None and start < hi and end > lo:
            out.setdefault(name, set()).add(where[1])
    return out
