"""Device time of a traced generation call by the Program's op, for the
readers of the share cell (benchmark/drivers/decode_share.py).

`apply_op` opens the op type's scope and the instance's inside it under
the decoder's scans as under the executor, but a scan puts its own
scopes in front (`jit(<lambda>)/while/body/closed_call/mla_cached_attention/
~mla_cached_attention_0.tmp_0/mla_scores/dot_general`), so the op type
is the component before the instance's and not the path's first (as
layer_metrics/decode_attention_ms_per_step.py reads it).  None for a
run without a chip, a trace, or the share driver's facts.
"""

import functools

from benchmark.reduce import op_instances, op_scopes, program_spans, \
    scans, xplane


def parts(path, mark):
    """(op type, instance, the scopes inside the instance) of a path, or
    None for one under no op instance."""
    found = op_scopes.components(path)
    for at in range(1, len(found)):
        if found[at].startswith(mark):
            return found[at - 1], found[at], found[at + 1:]
    return None


@functools.lru_cache(maxsize=1)
def _operations(trace_dir, ordinal, window):
    paths = op_scopes.metadata_stat(xplane.find_xplane(trace_dir),
                                    "/device:TPU:%d" % ordinal, "tf_op")
    return op_scopes.scoped(program_spans.profile(trace_dir), paths,
                            ordinal, window)


def operations(run):
    """(the first device's operations with their paths, the instance
    sigil) of a traced run of the share driver on a chip, or None."""
    trace, mark = run.reduced, op_instances.sigil()
    if run.peaks is None or trace is None or not trace.devices \
            or mark is None or "share_step_applications" not in run.facts:
        return None
    return _operations(run.trace_dir, min(trace.devices),
                       trace.window), mark


def seconds(run, key, interval=None):
    """{key(op type, instance, inner scopes): [seconds, calls]} of the
    operations under an op instance, inside the traced window or inside
    `interval`; those for which `key` gives None are left out."""
    found = operations(run)
    if found is None:
        return None
    scoped, mark = found
    lo, hi = interval or scoped.window
    out = {}
    for start, end, _, path in scoped.ops:
        where = parts(path, mark)
        name = key(*where) if where is not None else None
        if name is None:
            continue
        for s, e in xplane.clip([(start, end)], lo, hi):
            entry = out.setdefault(name, [0.0, 0])
            entry[0] += e - s
            entry[1] += 1
    return out


def call_scans(run):
    """((start, end) of the traced call's prefill scan, of its decoding
    scan) on the first device, or None where the trace does not hold the
    two: the two longest of the outermost `while` operations, in the
    order they ran (a grouped product's list of visits is made by a
    `searchsorted`, a short `while` of its own: the prompt's first
    position, which runs before the prefill's scan, has one a layer)."""
    trace = run.reduced
    if trace is None or not trace.devices:
        return None
    found = scans.outermost(trace.devices[min(trace.devices)], trace.window)
    if len(found) < 2:
        return None
    return tuple(sorted(sorted(found, key=lambda s: s[0] - s[1])[:2]))


def decoding_steps(run):
    """(the decoding scan's interval, its steps), or None."""
    found = call_scans(run)
    steps = run.facts.get("share_gen_len", 0) - 1
    if found is None or steps < 1:
        return None
    return found[1], steps
