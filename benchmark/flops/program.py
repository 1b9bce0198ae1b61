"""Operations a training or inference step requires, counted from the
shapes in the program's IR: a copy of the arithmetic of
`paddle_tpu/fluid/analysis.py` (`_conv_flops`, `_mul_flops`), kept here
so that a change to the program cannot change the yardstick, with two
differences.  A gradient op counts one contraction per gradient it
actually produces (the first convolution has no input gradient), and the
`flash_attention` op is counted by benchmark/flops/flash.py.

One multiply-add is two FLOPs.  Recomputation is never counted.
"""

from benchmark.flops import flash

_CONV = {"conv2d", "conv3d", "depthwise_conv2d"}
_MATMUL = {"mul", "matmul"}
_GRAD = "_grad"
_EMPTY = "@EMPTY@"


def _numel(shape):
    n = 1
    for s in shape:
        n *= int(s)
    return n


def _shape(block, names):
    if not names or names[0] == _EMPTY:
        return None
    shape = block.var_recursive(names[0]).shape
    if shape is None or any(int(s) < 0 for s in shape):
        raise ValueError("flops: %r has no static shape" % names[0])
    return [int(s) for s in shape]


def _produced(od, slots):
    """How many of the gradient slots the op really writes."""
    return sum(1 for s in slots
               if od.outputs.get(s) and od.outputs[s][0] != _EMPTY)


def _conv(block, od, forward):
    out = _shape(block, od.output("Output") if forward
                 else od.input("O@Output"))
    w = _shape(block, od.input("Filter"))
    # filter [K, C/groups, *kernel]: C/groups * prod(kernel) multiply-adds
    # per output element
    one = 2 * _numel(out) * (_numel(w) // w[0])
    if forward:
        return one
    return one * _produced(od, ("Input@GRAD", "Filter@GRAD"))


def _matmul(block, od, forward):
    out = _shape(block, od.output("Out") if forward else od.input("O@Out"))
    y = _shape(block, od.input("Y"))
    one = 2 * _numel(out) * (_numel(y) // y[-1])
    if forward:
        return one
    return one * _produced(od, ("X@GRAD", "Y@GRAD"))


def _attention(block, od, forward):
    q = _shape(block, od.input("Q"))
    k = _shape(block, od.input("K"))
    heads = int(od.attrs.get("num_heads", 1))
    causal = bool(od.attrs.get("causal", False))
    dims = (q[0], heads, q[1], k[1], q[2] // heads, causal)
    if forward:
        return flash.forward_cost(*dims)
    return flash.backward_flops(*dims)


def program_flops(program):
    """FLOPs of one run of the program's global block:
    {"total", "mxu", "kernels": {name: {"flops", "bytes", "calls"}}}.

    "mxu" is what XLA runs as convolutions and matrix products: every
    conv and matmul op, forward and backward, and the attention
    backward (plain XLA today).  "kernels" holds what runs as a named
    Pallas kernel; "total" is both, the model FLOPs of the step."""
    block = program.global_block()
    mxu = 0
    kernels = {}
    for od in block.desc.ops:
        forward = not od.type.endswith(_GRAD)
        base = od.type if forward else od.type[:-len(_GRAD)]
        if base in _CONV:
            mxu += _conv(block, od, forward)
        elif base in _MATMUL:
            mxu += _matmul(block, od, forward)
        elif base == "flash_attention":
            cost = _attention(block, od, forward)
            if forward:
                entry = kernels.setdefault(
                    flash.KERNEL_NAME, {"flops": 0, "bytes": 0, "calls": 0})
                entry["flops"] += cost["flops"]
                entry["bytes"] += cost["bytes"]
                entry["calls"] += 1
            else:
                mxu += cost
    total = mxu + sum(k["flops"] for k in kernels.values())
    return {"total": total, "mxu": mxu, "kernels": kernels}
