"""Operations and bytes of the flash-attention forward kernel
(`paddle_tpu/kernels/flash_attention.py`, pallas_call name
`flash_attention_fwd`), from its shapes alone, and the least time a chip
with given peaks could take for them.
"""

KERNEL_NAME = "flash_attention_fwd"


def attended_pairs(seq_q, seq_k, causal):
    """(query, key) pairs whose score enters a softmax."""
    if not causal:
        return seq_q * seq_k
    # query i of a causal square sees keys 0..i; a longer key side is
    # seen whole up to the diagonal's start
    offset = seq_k - seq_q
    return sum(min(seq_k, offset + i + 1) for i in range(seq_q))


def forward_cost(batch, heads, seq_q, seq_k, head_dim, causal,
                 itemsize=2):
    """FLOPs and bytes one forward call needs: q.k^T and p.v are two
    products of 2*head_dim FLOPs per attended pair; q, k and v are read
    and o written once in the compute type, and the two float32 row
    statistics (m, l) written once."""
    pairs = attended_pairs(seq_q, seq_k, causal)
    flops = 4 * batch * heads * pairs * head_dim
    rows_q, rows_k = batch * heads * seq_q, batch * heads * seq_k
    nbytes = ((2 * rows_q + 2 * rows_k) * head_dim * itemsize
              + 2 * rows_q * 4)
    return {"flops": flops, "bytes": nbytes}


def backward_flops(batch, heads, seq_q, seq_k, head_dim, causal):
    """FLOPs the backward pass requires: dv, dp, dq and dk are four
    products of 2*head_dim per attended pair.  Recomputing the scores
    is not counted."""
    return 8 * batch * heads * attended_pairs(seq_q, seq_k, causal) \
        * head_dim


def roofline(cost, peaks):
    """Least seconds for `cost` on a chip with `peaks`, and which of the
    two bounds it: ("compute" | "memory")."""
    t_flops = cost["flops"] / peaks["bf16_flops_per_s"]
    t_bytes = cost["bytes"] / peaks["hbm_bytes_per_s"]
    return max(t_flops, t_bytes), \
        ("compute" if t_flops >= t_bytes else "memory")
