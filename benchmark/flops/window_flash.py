"""Operations and bytes of the flash-attention kernels under a window
(`paddle_tpu/kernels/flash_attention.py`, pallas_call names
`flash_attention_fwd_*_w<W>*`, `flash_attention_bwd_*_w<W>*`), from
shapes alone: what benchmark/flops/flash.py counts, with the attended
pairs those a causal query keeps of its last `window` keys.

A query at position i of a causal square attends keys max(0, i - W + 1)
.. i: min(i + 1, W) of them.  The forward is two products of 2 *
head_dim FLOPs an attended pair (q.k^T, p.v), the backward four (dv, dp,
dq, dk); recomputing the scores in the backward is the kernels' own cost
and is not counted, nor are the masked halves of the chunks either edge
crosses.  One multiply-add is two FLOPs.
"""

import re

# the kernels whose name carries a window, forward and backward
FWD_NAME = re.compile(r"^flash_attention_fwd\w*_w\d+")
BWD_NAME = re.compile(r"^flash_attention_bwd\w*_w\d+")
OP_TYPE = "flash_attention"


def attended_pairs(seq, window):
    """(query, key) pairs whose score enters a softmax, of a causal
    square of `seq` positions under `window` (0: no window)."""
    if not window or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def forward_cost(batch, heads, seq, head_dim, window, itemsize=2):
    """FLOPs and bytes one forward call needs: two products an attended
    pair; q, k and v read and o written once in the compute type, the
    two float32 row statistics written once."""
    rows = batch * heads * seq
    return {"flops": 4 * batch * heads * attended_pairs(seq, window)
            * head_dim,
            "bytes": 4 * rows * head_dim * itemsize + 2 * rows * 4}


def backward_cost(batch, heads, seq, head_dim, window, itemsize=2):
    """FLOPs and bytes the backward of one call needs: four products an
    attended pair; q, k, v and do read and dq, dk, dv written once, the
    two float32 row statistics (lse, delta) read once."""
    rows = batch * heads * seq
    return {"flops": 8 * batch * heads * attended_pairs(seq, window)
            * head_dim,
            "bytes": 7 * rows * head_dim * itemsize + 2 * rows * 4}


def program_cost(program, itemsize=2):
    """{"window", "full"}: {"forward", "backward"}: {"flops", "bytes",
    "calls"} a step of the program's `flash_attention` ops and of their
    gradient ops requires, from the shapes in its IR: those that carry a
    window a query reaches past, and the others.  Zeros where it has
    none."""
    block = program.global_block()
    total = {kind: {which: {"flops": 0, "bytes": 0, "calls": 0}
                    for which in ("forward", "backward")}
             for kind in ("window", "full")}
    for od in block.desc.ops:
        forward = od.type == OP_TYPE
        if not forward and od.type != OP_TYPE + "_grad":
            continue
        batch, seq, width = (
            int(s) for s in block.var_recursive(od.input("Q")[0]).shape)
        heads = int(od.attrs.get("num_heads", 1))
        window = int(od.attrs.get("window", 0))
        if not bool(od.attrs.get("causal", False)):
            raise ValueError("window_flash: %s is not causal" % od.type)
        cost = (forward_cost if forward else backward_cost)(
            batch, heads, seq, width // heads, window, itemsize)
        into = total["window" if 0 < window < seq else "full"][
            "forward" if forward else "backward"]
        into["flops"] += cost["flops"]
        into["bytes"] += cost["bytes"]
        into["calls"] += 1
    return total


def roofline(cost, peaks):
    """Least seconds for `cost` on a chip with `peaks`, and which of the
    two bounds it: ("compute" | "memory")."""
    t_flops = cost["flops"] / peaks["bf16_flops_per_s"]
    t_bytes = cost["bytes"] / peaks["hbm_bytes_per_s"]
    return max(t_flops, t_bytes), \
        ("compute" if t_flops >= t_bytes else "memory")
