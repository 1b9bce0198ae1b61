"""Device time by the op of the Program, not only by its type.

benchmark/reduce/op_scopes.py reads an operation's `op_name` path and
takes its first component that is no `jit(...)` wrapper as the op type.
Since PR 33 `fluid.executor.apply_op` opens a second scope inside the
type's, so a path reads
`jit(segment_fn)/conv2d/~conv2d_7.tmp_0/conv_general_dilated`: the
second component is the op's *instance*, a sigil and the name of a
variable the op is bound to.  The rule is the program's and lives in one
place, `paddle_tpu.fluid.executor.op_instance` (a forward op: its first
output; a `<type>_grad` op: its forward's first output, so the two join;
an op that updates a parameter: the parameter), with the sigil beside it
(`INSTANCE_SIGIL`); benchmark/flops/instances.py keys the FLOPs of the
same ops by the same function.  This module only reads: the component
right after the type, if it starts with the sigil.  A program from
before the scope has neither name, and everything here gives None for
it.  The compile cache's key leaves `op_name` out, so a step program
that a checkout from before PR 33 compiled into the same cache carries
no instance either: such a run reads a share of 0, not None.

Two ops of one type that write one variable in place share an instance;
`shared()` counts them from the program's ops, nothing fails on them.

A `conv2d_grad` instance holds two convolutions, the filter's gradient
and the input's.  The trace says which an operation is by the shape it
writes (`written_shapes`, from the text of the HLO instruction): the
filter's dimensions or the input's, in whatever order XLA laid them out.
An operation that writes both, or neither, is a fusion that holds both
or something else (a cast of the filter, a copy), and is counted apart.
"""

import collections
import functools
import re

from benchmark.flops import elementwise
from benchmark.reduce import op_scopes, xplane

RESULT = re.compile(r"^%\S+ = (.*?) [a-z][a-z0-9-]*\(")
SHAPE = re.compile(r"\b[a-z]+\d*\[([\d,]*)\]")
WEIGHT, INPUT, BOTH, OTHER = "weight", "input", "both", "other"


def sigil():
    """The program's instance sigil, or None for a program without."""
    from paddle_tpu.fluid import executor

    return getattr(executor, "INSTANCE_SIGIL", None)


@functools.lru_cache(maxsize=None)
def type_and_instance(path, mark):
    """(op type, instance) of a path, the instance None where the path
    has none; None for a path under no op type.  (Kept, as
    `op_scopes.components` is: a few thousand paths, a few hundred
    thousand operations.)"""
    parts = [p for p in op_scopes.components(path)
             if not op_scopes.JIT_WRAPPER.match(p)]
    if len(parts) < 2 or not parts[0]:
        return None
    return parts[0], parts[1] if parts[1].startswith(mark) else None


@functools.lru_cache(maxsize=1)
def _seconds(run, mark):
    scoped = op_scopes.of_run(run)
    if scoped is None:
        return None
    text_of = texts(run)
    category = {name: xplane.parse_instruction(text)[1]
                for name, text in text_of.items()}
    moved = {}
    found = collections.defaultdict(lambda: [0.0, 0, 0.0, 0, 0])
    for start, end, name, path in scoped.ops:
        key = type_and_instance(path, mark)
        if key is None:
            continue
        for s, e in xplane.clip([(start, end)], *scoped.window):
            entry = found[key]
            entry[0] += e - s
            entry[1] += 1
            if category.get(name) in xplane.MXU_CATEGORIES:
                entry[2] += e - s
                entry[3] += 1
            if name not in moved:
                moved[name] = elementwise.instruction_bytes(
                    text_of.get(name, ""))
            entry[4] += moved[name]
    return dict(found)


def seconds(run):
    """{(op type, instance or None): [seconds, calls, the seconds and
    calls of them in the MXU's categories (benchmark/reduce/xplane.py),
    the bytes of HBM traffic the instructions' texts state
    (benchmark/flops/elementwise.py `instruction_bytes`)]} of the first
    device's traced window, or None: no trace, no op scopes, or a
    program without instance scopes."""
    mark = sigil()
    return None if mark is None else _seconds(run, mark)


def named_share(found):
    """Of the device seconds under any op type, the share that also lies
    under an instance."""
    total = sum(entry[0] for entry in found.values())
    named = sum(entry[0] for (_, inst), entry in found.items()
                if inst is not None)
    return named / total if total else None


def base_type(kind):
    return kind[:-len("_grad")] if kind.endswith("_grad") else kind


def joined(found, optimizers=()):
    """{(base type, instance): {"forward", "backward", "optimizer":
    seconds}}: an op and its gradient under one key."""
    out = collections.defaultdict(
        lambda: {"forward": 0.0, "backward": 0.0, "optimizer": 0.0})
    for (kind, inst), entry in found.items():
        if inst is None:
            continue
        which = op_scopes.pass_of(kind, optimizers)
        out[base_type(kind), inst][which] += entry[0]
    return out


def shared(program):
    """{(op type, instance): count} of the instances more than one op of
    the program's global block has."""
    from paddle_tpu.fluid import executor

    counts = collections.Counter(
        (od.type, executor.op_instance(od))
        for od in program.global_block().desc.ops)
    return {key: n for key, n in counts.items() if n > 1}


# -- the instructions' own text ------------------------------------------------

@functools.lru_cache(maxsize=1)
def _texts(trace_dir, ordinal):
    paths = op_scopes.metadata_stat(xplane.find_xplane(trace_dir),
                                    "/device:TPU:%d" % ordinal, "tf_op")
    return {xplane.parse_instruction(text)[0]: text for text in paths}


def texts(run):
    """{instruction name: its HLO text} of the first device."""
    return _texts(run.trace_dir, min(run.reduced.devices))


def written_shapes(text):
    """The dimensions of every array an instruction writes:
    "%f = (f32[256]{0}, bf16[128,256,56,56]{1,0,3,2}) fusion(..." gives
    [(256,), (128, 256, 56, 56)]."""
    match = RESULT.match(text)
    if not match:
        return []
    return [tuple(int(d) for d in dims.split(",") if d)
            for dims in SHAPE.findall(match.group(1))]


def _same_dims(a, b):
    """Whether two shapes are one up to order and to axes of length 1."""
    return sorted(d for d in a if d != 1) == sorted(d for d in b if d != 1)


def gradient_kind(text, filter_shape, input_shape):
    """Which gradient of a convolution an operation under its
    `conv2d_grad` instance writes."""
    shapes = written_shapes(text)
    weight = any(_same_dims(s, filter_shape) for s in shapes)
    inp = any(_same_dims(s, input_shape) for s in shapes)
    if weight and inp:
        return BOTH
    return WEIGHT if weight else INPUT if inp else OTHER


def conv_grad_seconds(run, shapes, grad_type="conv2d_grad"):
    """{instance: {"weight" | "input" | "both" | "other": seconds}} of
    the operations under `grad_type`; `shapes` is {instance: (filter
    shape, input shape)} from the program."""
    mark = sigil()
    scoped = op_scopes.of_run(run)
    if mark is None or scoped is None:
        return None
    text_of = texts(run)
    out = collections.defaultdict(lambda: collections.Counter())
    lo, hi = scoped.window
    for start, end, name, path in scoped.ops:
        found = type_and_instance(path, mark)
        if found is None or found[0] != grad_type or found[1] not in shapes:
            continue
        clipped = xplane.clip([(start, end)], lo, hi)
        if not clipped:
            continue
        kind = gradient_kind(text_of.get(name, ""), *shapes[found[1]])
        out[found[1]][kind] += xplane.length(clipped)
    return out
