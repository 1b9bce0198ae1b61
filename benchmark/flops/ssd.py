"""Operations and bytes the chunked selective state-space scan
(`ssd_scan`, paddle_tpu/ops/ssm.py; arXiv:2405.21060 section 6) and its
gradient require, from shapes alone.

With `Q` the chunk, `N` the state size, `P` a head's width, `H` heads
that share one B and C, per chunk of one sequence, forward:

    C B^T, the causal half            Q (Q + 1) / 2 * N   multiply-adds, once
    (L . C B^T) (dt X), causal half   Q (Q + 1) / 2 * P   a head
    C S_in^T                          Q * N * P           a head
    the chunk's state, X^T B          Q * N * P           a head

and backward, its own count, not a multiple of the forward's: dY (dt
X)^T and its transpose's product with dY (causal halves, 2 x Q (Q + 1) /
2 * P a head), the two products that turn d(C B^T) into dB and dC
(causal halves, 2 x Q (Q + 1) / 2 * N, once), and four state products a
head (the state's cotangent, dC from the entering state, d(dt X) and dB
from the leaving state's cotangent: 4 x Q * N * P).  A kernel that
computes whole [Q, Q] tiles, or `C B^T` again in the gradient, does more
than this and reads lower; recomputation is never counted.  One
multiply-add is two FLOPs.  The exponentials of the decay mask (Q (Q +
1) / 2 a head and chunk) are counted apart: they run on another unit.

Bytes: every operand read once and every result written once.  Forward
X, B, C in the compute type, Dt float32, Y written; the chunk states the
gradient needs are the algorithm's own and are not counted.  Backward X,
B, C, Y, dY and the entering states read, dX, dB, dC, dDt written.
"""

OP_TYPE = "ssd_scan"
KERNEL_PREFIX = "ssd_"


def scan_cost(batch, seq, heads, head_dim, d_state, chunk, itemsize=2):
    """{"forward", "backward"}: {"flops", "bytes", "exps"} of one scan
    over [batch, seq, heads * head_dim]."""
    chunks = batch * (seq // chunk)
    half = chunk * (chunk + 1) // 2
    state = chunk * d_state * head_dim
    fwd = 2 * chunks * (half * d_state
                        + heads * (half * head_dim + 2 * state))
    bwd = 2 * chunks * (2 * half * d_state
                        + heads * (2 * half * head_dim + 4 * state))
    tokens = batch * seq
    width = heads * head_dim
    wide = tokens * width * itemsize
    narrow = tokens * d_state * itemsize
    steps = tokens * heads * 4
    states = chunks * d_state * width * 4
    return {
        "forward": {"flops": fwd, "exps": chunks * heads * half,
                    "bytes": 2 * wide + 2 * narrow + steps},
        "backward": {"flops": bwd, "exps": chunks * heads * half,
                     "bytes": 4 * wide + 2 * narrow + states
                     + 2 * tokens * d_state * 4 + 2 * steps},
    }


def program_cost(program, itemsize=2):
    """{"flops", "bytes", "exps", "scans"} a step of the program's
    `ssd_scan` ops and their gradients requires, from the shapes in its
    IR; zeros where it has none."""
    block = program.global_block()
    total = {"flops": 0, "bytes": 0, "exps": 0, "scans": 0}
    for od in block.desc.ops:
        forward = od.type == OP_TYPE
        if not forward and od.type != OP_TYPE + "_grad":
            continue
        batch, seq, width = (
            int(s) for s in block.var_recursive(od.input("X")[0]).shape)
        heads = int(block.var_recursive(od.input("Dt")[0]).shape[-1])
        d_state = int(block.var_recursive(od.input("B")[0]).shape[-1])
        cost = scan_cost(batch, seq, heads, width // heads, d_state,
                         int(od.attrs["chunk_size"]), itemsize)[
            "forward" if forward else "backward"]
        for key in ("flops", "bytes", "exps"):
            total[key] += cost[key]
        total["scans"] += forward
    return total


def roofline(cost, peaks):
    """Least seconds for `cost` on a chip with `peaks`, and which of the
    two bounds it: ("compute" | "memory")."""
    t_flops = cost["flops"] / peaks["bf16_flops_per_s"]
    t_bytes = cost["bytes"] / peaks["hbm_bytes_per_s"]
    return max(t_flops, t_bytes), \
        ("compute" if t_flops >= t_bytes else "memory")
