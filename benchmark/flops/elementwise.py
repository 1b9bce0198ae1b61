"""Bytes of memory-bound ops, counted two ways.

`program_bytes`: what the normalisation and rotary ops of a program would
move as kernels of their own, from the shapes in its IR: each operand
read once and each result written once, in the compute type.  `rms_norm`
reads x and writes y; its gradient reads x and dy and writes dx.  `rope`
reads x and writes y; its gradient turns dy back and needs no x.  The
scale vector, its gradient and the positions are a few kilobytes and are
not counted.

`instruction_bytes`: what one instruction of the compiled program does
move, from the text of its HLO instruction as the profiler's trace holds
it: its results and its operands, each once, at their own types.  XLA
fuses such ops into their neighbours where it can, and what it fused
away is no instruction of its own; so the second count, against the
device time of the same instructions, is a share of the HBM peak that
was reached, and the first says what fusion already saved.
"""

import re

_GRAD = "_grad"
# arrays of X's size moved by one op: (forward, gradient)
_ARRAYS = {"rms_norm": (2, 3), "rope": (2, 2)}


def _numel(block, name):
    shape = block.var_recursive(name).shape
    if shape is None or any(int(s) < 0 for s in shape):
        raise ValueError("bytes: %r has no static shape" % name)
    n = 1
    for s in shape:
        n *= int(s)
    return n


def program_bytes(program, itemsize):
    """{"total", "ops": {op type: {"bytes", "calls"}}} of one run of the
    program's global block, for the op types this file knows."""
    block = program.global_block()
    ops = {}
    for od in block.desc.ops:
        forward = not od.type.endswith(_GRAD)
        base = od.type if forward else od.type[:-len(_GRAD)]
        if base not in _ARRAYS:
            continue
        arrays = _ARRAYS[base][0 if forward else 1]
        entry = ops.setdefault(od.type, {"bytes": 0, "calls": 0})
        entry["bytes"] += arrays * _numel(block, od.input("X")[0]) * itemsize
        entry["calls"] += 1
    return {"total": sum(e["bytes"] for e in ops.values()), "ops": ops}


# "bf16[1,4096,2048]{2,1,0:T(8,128)(2,1)}": type, dimensions, layout
_SHAPE = re.compile(r"\b(pred|[a-z]+\d+)\[([\d,]*)\](\{[^}]*\})?")
_BITS = re.compile(r"\d+$")
# a layout's memory space: S(1) and up are on-chip, not HBM
_ON_CHIP = re.compile(r"S\([1-9]")


def instruction_bytes(text):
    """Bytes of HBM traffic of one HLO instruction, from its text: every
    array among its results and operands once.  An array the layout
    places on-chip ("S(1)") is not counted; a token or an opaque type has
    no size."""
    total = 0
    for dtype, dims, layout in _SHAPE.findall(text):
        if layout and _ON_CHIP.search(layout):
            continue
        bits = 8 if dtype == "pred" else int(_BITS.search(dtype).group())
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * max(bits, 8) // 8
    return total
