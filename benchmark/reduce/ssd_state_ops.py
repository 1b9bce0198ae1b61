"""A traced run of the Mamba-2 state cell (benchmark/drivers/
decode_ssd_state.py) as benchmark/reduce/state_ops.py reads one: the
same account of a call (the `decode/call` span, the `decode_steps` and
`decode_prefill` scopes, an op's path), for a run that carries that
driver's facts (`ssd_state_*`) and not decode_state.py's, whose readers
count a delta-rule share's sizes from keys this configuration does not
have and find nothing to read here.

`view(run)` is the run with those facts under the names state_ops knows
(`state_batch`, `state_prompt_len`, `state_gen_len`), or None for a run
of another driver (or of a program from before `ssd_scan` carried a
state: such a program cannot build the cell, and no fact is there).
"""

from benchmark.reduce import state_ops

_FACTS = ("batch", "prompt_len", "gen_len")


class _View:
    """A run's fields with other facts (hashable: state_ops keeps what
    it made of one)."""

    def __init__(self, run, facts):
        self.__dict__.update(vars(run), facts=facts)


def view(run):
    if "ssd_state_gen_len" not in run.facts:
        return None
    if not hasattr(run, "_ssd_as_state"):   # made once a run
        run._ssd_as_state = _View(run, dict(run.facts, **{
            "state_" + name: run.facts["ssd_state_" + name]
            for name in _FACTS}))
    return run._ssd_as_state


def traced(run):
    """The view of a run that holds a traced call with a scan of steps,
    or None."""
    seen = view(run)
    return seen if seen is not None and state_ops.calls(seen) is not None \
        else None


def step_seconds(run, key):
    """state_ops.step_seconds over the view: {key(op type, instance,
    inner scopes): seconds a decoding step}, or None."""
    seen = traced(run)
    return None if seen is None else state_ops.step_seconds(seen, key)


def prefill_seconds(run, key):
    seen = traced(run)
    return None if seen is None else state_ops.prefill_seconds(seen, key)


def instances(run, op_type, wanted):
    return state_ops.instances(view(run), op_type, wanted)


def step_ops(run):
    """The op descs of the cell's step Program."""
    return state_ops._step_ops(view(run))


def device_step_seconds(run):
    seen = traced(run)
    return None if seen is None else state_ops.device_step_seconds(seen)


def mean_decode_position(run):
    return state_ops.mean_decode_position(view(run))


# what a decoding step's device time is split by: the op types of the
# step Program and, for the `mul` ops, the parameter they read
_OPS = {"ssd_scan": "scan", "causal_conv1d": "convolution with its tail",
        "moe_router": "moe_router", "moe_experts": "moe_experts",
        "cached_attention": "cached_attention"}
_MULS = (((".in_proj", ".out_proj"), "mamba projections"),
         ((".shared_in", ".shared_out"), "shared expert"),
         ((".wq", ".wk", ".wv", ".wo"), "attention projections"))
_NAMED = "ssd_gated_norm"
SCAN_SCOPES = ("ssd_decay", "ssd_step", "ssd_chunks")


def _part_of(run):
    reads = {name: instances(
        run, "mul", lambda od, ends=ends: od.input("Y")[0].endswith(ends))
        for ends, name in _MULS}

    def part(kind, instance, inner):
        if kind in _OPS:
            return _OPS[kind]
        if instance[1:].startswith(_NAMED):
            return "gated norm"
        for name, found in reads.items():
            if kind == "mul" and instance in found:
                return name
        return None
    return part


def step_split(run):
    """{part: seconds a decoding step} of the traced calls' scans of
    steps, first device: the mamba mixers' scan (`ssd_scan`), their
    convolution, gated norm (the instances the builder names
    `ssd_gated_norm`) and two projections; the expert layers' router,
    held experts and shared expert; the attention layers' op and four
    projections.  None without a traced call."""
    if traced(run) is None:
        return None
    return step_seconds(run, _part_of(run))


def prefill_split(run):
    """The same parts inside a call's prefill, seconds a call."""
    if traced(run) is None:
        return None
    return prefill_seconds(run, _part_of(run))


def scan_scopes(run, seconds=step_seconds):
    """{scope: seconds} of the operations under the `ssd_scan` op, by
    its scopes (`ssd_decay`: the float32 steps and decays; `ssd_step`:
    the update of the state; `ssd_chunks`: a block's chunks, kernels/ssd.py's `ssd_block_*`
    or plain products), a decoding step, or with
    `seconds=prefill_seconds` a call's prefill."""
    def scope(kind, instance, inner):
        if kind != "ssd_scan":
            return None
        named = [p for p in inner if p in SCAN_SCOPES]
        return named[0] if named else "(no scope)"
    return seconds(run, scope)


def said(what, found):
    """One readable line: the parts of `found`, ms, and their sum."""
    print("%s: %s; in all %.4f" % (what, ", ".join(
        "%s %.4f" % (name, s * 1e3) for name, s in sorted(found.items())),
        sum(found.values()) * 1e3), flush=True)
