"""A traced run of the dense state cell (benchmark/drivers/
decode_dense_state.py) as benchmark/reduce/state_ops.py reads one: the
same account of a call (the `decode/call` span, the `decode_steps` and
`decode_prefill` scopes, an op's path), for a run that carries the dense
state driver's facts (`dense_state_*`) and not decode_state.py's.

`view(run)` is the run with those facts under the names state_ops and
the readers written on it know (`state_batch`, `state_prompt_len`,
`state_gen_len`), or None for a run of another driver.  The readers of
the rule's metrics (`gdn_ms_per_step`, `gdn_step_roofline`,
`gdn_prefill_ms_per_call`) read sizes this cell's configuration has, and
its `dense_gdn_*` namesakes hand them the view; the readers of the
expert share's other metrics count routed experts this configuration
has none of, and are not asked.
"""

from benchmark.reduce import state_ops

_FACTS = ("batch", "prompt_len", "gen_len")


class _View:
    """A run's fields with other facts (hashable: state_ops keeps what
    it made of one)."""

    def __init__(self, run, facts):
        self.__dict__.update(vars(run), facts=facts)


def view(run):
    if "dense_state_gen_len" not in run.facts:
        return None
    if not hasattr(run, "_as_state"):   # made once a run
        run._as_state = _View(run, dict(run.facts, **{
            "state_" + name: run.facts["dense_state_" + name]
            for name in _FACTS}))
    return run._as_state


def traced(run):
    """The view of a run that holds a traced call with a scan of steps,
    or None."""
    seen = view(run)
    return seen if seen is not None and state_ops.calls(seen) is not None \
        else None


def as_state(run, reader):
    """What the state cell's reader `reader` reads of this run, or
    None."""
    seen = view(run)
    if seen is None:
        return None
    return run.lookup.module("layer_metrics", reader).read(seen)


def step_seconds(run, key):
    """state_ops.step_seconds over the view: {key(op type, instance,
    inner scopes): seconds a decoding step}, or None."""
    seen = traced(run)
    return None if seen is None else state_ops.step_seconds(seen, key)


def instances(run, op_type, wanted):
    return state_ops.instances(view(run), op_type, wanted)


def step_ops(run):
    """The op descs of the cell's step Program."""
    return state_ops._step_ops(view(run))


def kernel_step_seconds(run, prefix):
    seen = view(run)
    return None if seen is None \
        else state_ops.kernel_step_seconds(seen, prefix)


def device_step_seconds(run):
    seen = view(run)
    return None if seen is None else state_ops.device_step_seconds(seen)


def mean_decode_position(run):
    return state_ops.mean_decode_position(view(run))
