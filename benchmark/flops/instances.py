"""The FLOPs of a step by the op that requires them: what
benchmark/flops/program.py adds up under "mxu" for the convolutions and
matrix products, kept apart by the op's instance
(`paddle_tpu.fluid.executor.op_instance`, the scope benchmark/reduce/
op_instances.py reads from the trace), with the same arithmetic (`_conv`,
`_matmul`, `_produced`): their sum is `program_flops`'s "mxu" less the
attention backward.  An op and its gradient are one entry: the gradient
op has its forward's instance.
"""

import functools

from benchmark.flops import program as counted

CONV, MATMUL = "conv", "matmul"
_GRAD = "_grad"


def _entry(kind):
    return {"kind": kind, "forward": 0, "backward": 0, "ops": 0,
            "gradients": 0}


def _conv_facts(block, od, forward):
    out = od.output("Output") if forward else od.input("O@Output")
    return {"filter": counted._shape(block, od.input("Filter")),
            "input": counted._shape(block, od.input("Input")),
            "output": counted._shape(block, out),
            "strides": [int(s) for s in od.attrs.get("strides", [1, 1])]}


def _matmul_facts(block, od, forward):
    out = od.output("Out") if forward else od.input("O@Out")
    return {"x": counted._shape(block, od.input("X")),
            "y": counted._shape(block, od.input("Y")),
            "output": counted._shape(block, out)}


def by_instance(program):
    """{(base op type, instance): {"kind": "conv" | "matmul", "forward",
    "backward": FLOPs, "ops": forward ops, "gradients": contractions the
    gradient op really produces, and the shapes}} of the global block, or
    None for a program without `op_instance`."""
    from paddle_tpu.fluid import executor

    name_of = getattr(executor, "op_instance", None)
    if name_of is None:
        return None
    block = program.global_block()
    found = {}
    for od in block.desc.ops:
        forward = not od.type.endswith(_GRAD)
        base = od.type if forward else od.type[:-len(_GRAD)]
        if base in counted._CONV:
            kind, cost, facts = CONV, counted._conv, _conv_facts
            slots = ("Input@GRAD", "Filter@GRAD")
        elif base in counted._MATMUL:
            kind, cost, facts = MATMUL, counted._matmul, _matmul_facts
            slots = ("X@GRAD", "Y@GRAD")
        else:
            continue
        entry = found.setdefault((base, name_of(od)), _entry(kind))
        entry.update(facts(block, od, forward))
        if forward:
            entry["forward"] += cost(block, od, True)
            entry["ops"] += 1
        else:
            entry["backward"] += cost(block, od, False)
            entry["gradients"] += counted._produced(od, slots)
    return found


@functools.lru_cache(maxsize=1)
def program_of(run):
    """The cell's training program, built once more for its shapes and
    names (the driver does not keep it; a Program's names are its own,
    so the instances are the traced program's)."""
    cfg = run.config
    return run.lookup.module("models", cfg["builder"]).build(
        cfg, run.workload["batch"], train=True)["main"]


def of_run(run):
    """`by_instance` of the cell's training program."""
    return by_instance(program_of(run))
