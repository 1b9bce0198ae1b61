"""Operations and bytes of the grouped products of a routed expert layer
(`paddle_tpu/kernels/grouped_matmul.py`, pallas_call names
`moe_gmm_fwd_*`, `moe_gmm_dx_*`, `moe_gmm_dw_*`), from shapes alone.

A grouped product multiplies each of `rows` rows by the [k, n] matrix of
the one expert the row was routed to, so it requires 2 * rows * k * n
FLOPs whatever the group sizes are: with `rows` the rows really routed
(tokens * experts a token; nothing is dropped and nothing padded).  The
tiles a kernel masks, and a group boundary's second visit of a tile, are
the kernel's own cost and are not counted.  Its two gradients are
products of the same size: dx = dy @ w^T over the same rows, and dw[e] =
x_e^T @ dy_e summed over them.

The layer (`moe_experts`, ops/moe.py) is gate and up [hidden -> width]
and down [width -> hidden]: three products forward; backward a dx and a
dw for each, six.  Recomputation is never counted (the op recomputes
none).  One multiply-add is two FLOPs.
"""

KERNEL_PREFIX = "moe_gmm"
OP_TYPE = "moe_experts"


def product_flops(rows, k, n):
    return 2 * rows * k * n


def product_bytes(rows, experts, k, n, itemsize=2, kind="fwd"):
    """Bytes one product has to move once: the rows in and out and
    every expert's matrix, in the compute type; a dw is written in
    float32."""
    weights = experts * k * n
    if kind == "dw":
        return rows * (k + n) * itemsize + weights * 4
    return rows * (k + n) * itemsize + weights * itemsize


def layer_cost(rows, experts, hidden, width, itemsize=2):
    """{"forward", "backward"}: {"flops", "bytes", "products"} of one
    expert layer on `rows` routed rows."""
    one = product_flops(rows, hidden, width)
    args = (rows, experts, hidden, width, itemsize)
    forward = 3 * product_bytes(*args)
    backward = 3 * product_bytes(*args) + 3 * product_bytes(*args, kind="dw")
    return {"forward": {"flops": 3 * one, "bytes": forward, "products": 3},
            "backward": {"flops": 6 * one, "bytes": backward,
                         "products": 6}}


def program_cost(program, itemsize=2):
    """{"flops", "bytes", "products", "layers", "rows"} a step of the
    program's `moe_experts` ops and their gradients requires, from the
    shapes in its IR; zeros where it has none."""
    block = program.global_block()
    total = {"flops": 0, "bytes": 0, "products": 0, "layers": 0, "rows": 0}
    for od in block.desc.ops:
        forward = od.type == OP_TYPE
        if not forward and od.type != OP_TYPE + "_grad":
            continue
        experts, hidden, width = (
            int(s) for s in block.var_recursive(od.input("WGate")[0]).shape)
        kept = od.output("Xs") if forward else od.input("O@Xs")
        rows = int(block.var_recursive(kept[0]).shape[0])
        cost = layer_cost(rows, experts, hidden, width, itemsize)[
            "forward" if forward else "backward"]
        for key in ("flops", "bytes", "products"):
            total[key] += cost[key]
        if forward:
            total["layers"] += 1
            total["rows"] += rows
    return total


def roofline(cost, peaks):
    """Least seconds for `cost` on a chip with `peaks`, and which of the
    two bounds it: ("compute" | "memory")."""
    t_flops = cost["flops"] / peaks["bf16_flops_per_s"]
    t_bytes = cost["bytes"] / peaks["hbm_bytes_per_s"]
    return max(t_flops, t_bytes), \
        ("compute" if t_flops >= t_bytes else "memory")
