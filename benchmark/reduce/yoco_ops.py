"""Device time of a traced generation call of the decoder-hybrid-decoder
cell (benchmark/drivers/decode_yoco.py) by the Program's op, inside the
call's decoding scan: benchmark/reduce/share_ops.py's reduction (read it
for how a path names its op type, instance and inner scopes), for a run
that carries that driver's facts and no other's.  None for a run without
a chip, a trace, or those facts: the parent commit's, whose program
cannot build the cell, never gets here.
"""

import functools

from benchmark.reduce import op_instances, share_ops, xplane

parts = share_ops.parts
call_scans = share_ops.call_scans


def operations(run):
    """(the first device's operations with their paths, the instance
    sigil) of a traced run of the driver on a chip, or None."""
    trace, mark = run.reduced, op_instances.sigil()
    if run.peaks is None or trace is None or not trace.devices \
            or mark is None or "yoco_step_applications" not in run.facts:
        return None
    return share_ops._operations(run.trace_dir, min(trace.devices),
                                 trace.window), mark


def decoding_steps(run):
    """(the decoding scan's interval, its steps), or None."""
    found = call_scans(run)
    steps = run.facts.get("yoco_gen_len", 0) - 1
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
    first = facts["yoco_session_len"] + facts["yoco_prompt_len"]
    return first + (facts["yoco_gen_len"] - 2) / 2.0


@functools.lru_cache(maxsize=1)
def _step_ops(run):
    """The op descs of the cell's step Program, built once more to name
    its instances (once a run: several readers ask)."""
    program = run.lookup.module("models", run.workload["builder"]).build(
        run.config, run.workload["batch"])["main"]
    return list(program.global_block().desc.ops)


def attention_instances(run):
    """{"window" | "full" | "cross": (the instances of that kind's
    `cached_attention` ops, of the `diff_combine` ops that follow them)}
    of the cell's step Program: a `diff_combine` belongs to the layer
    whose attention output it reads."""
    from paddle_tpu.fluid import executor

    found = {kind: (set(), set()) for kind in ("window", "full", "cross")}
    kind_of = {}
    for od in _step_ops(run):
        if od.type == "cached_attention":
            kind = "cross" if "KNew" not in od.inputs \
                else "window" if od.attrs.get("window", 0) else "full"
            kind_of[od.output("Out")[0]] = kind
            found[kind][0].add(executor.op_instance(od))
        elif od.type == "diff_combine":
            found[kind_of[od.input("X")[0]]][1].add(
                executor.op_instance(od))
    return found
