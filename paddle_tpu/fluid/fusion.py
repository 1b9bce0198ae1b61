"""Fused-op program rewrites: optimizer-update stacking and
elementwise-chain fusion.

The whole-block executor compiles a train step into one XLA program,
but each per-parameter update op still lowers to its own fusion kernel
on device — ~160 kernel launches per step on ResNet-50, a few
microseconds of elementwise math each, so launch overhead dominates.
This pass groups update ops that share a recipe — same op type, same
hyperparameter attrs, same learning-rate input, same dtype — and
rewrites each group into one ``fused_update`` op whose kernel
concatenates the flattened parameters, applies the recipe once over the
concatenation, and splits the results back.  All eleven update recipes
are purely elementwise in their per-parameter tensors, so per-lane
values are unchanged: results are bit-identical wherever the backend
lowers the recipe with exactly-rounded ops (asserted bitwise for
sgd/momentum/adagrad/rmsprop/adadelta in tests/test_fused_optimizer.py;
adam's rsqrt lowering on the CPU backend is lane-position-dependent and
may move by a few ulp).

The reference reaches the same end on GPU with hand-written fused
training kernels (reference: paddle/math/TrainingAlgorithmOp.cu); here
it is a program rewrite over the op IR, so it applies to every
optimizer uniformly and can be undone: ``unfuse_update_ops`` expands
fused ops back to per-parameter ops (the distribute transpiler does
this first so updates can be scattered across parameter servers).

The second rewrite, ``fuse_elemwise_chains``, targets the OTHER fused
family: straight-line chains of elementwise/activation/bias ops (a
residual ``elementwise_add`` feeding its ``relu``, a bias add feeding
an activation) collapse into one ``fused_elemwise_chain`` op whose
kernel (ops/math.py) applies the original registered kernels in
sequence — per-lane numerics identical by construction.  The chain's
intermediate tensors disappear from the IR entirely, which is what
moves the roofline's unique-bytes HBM floor (fluid/analysis.py) and
shrinks the op count the segmenter/verifier walk.  It is the engine
of the `fuse` rewrite pass (compile/opt_passes.py).
"""

import json
from collections import OrderedDict

from ..core.desc import OpDesc, _attr_to_jsonable
from ..core.types import FUSED_ELEMWISE_OP
from ..utils import flags
from .backward import EMPTY

__all__ = ["PER_PARAM_UPDATE_OPS", "FUSED_UPDATE_OP", "fuse_update_ops",
           "unfuse_update_ops", "FUSED_ELEMWISE_OP", "FUSABLE_UNARY",
           "FUSABLE_BINARY", "fuse_elemwise_chains"]

# every registered per-parameter update op (ops/optimizer_ops.py)
PER_PARAM_UPDATE_OPS = frozenset([
    "sgd", "momentum", "adam", "adamax", "adagrad", "decayed_adagrad",
    "adadelta", "rmsprop", "ftrl", "proximal_gd", "proximal_adagrad"])

FUSED_UPDATE_OP = "fused_update"

# attrs the fused op adds on top of the inner recipe's own attrs
_FUSION_ATTRS = ("inner_type", "stacked_slots")

# input slots holding cross-parameter scalar state ([1]-shaped, shared by
# every op one optimizer instance emits).  These can never be stacked —
# two instances' ops must land in different groups — so they join the
# recipe key alongside LearningRate.
_SHARED_STATE_SLOTS = {
    "adam": ("Beta1Pow", "Beta2Pow"),
    "adamax": ("Beta1Pow",),
}


def _freeze(value):
    return tuple(value) if isinstance(value, list) else value


def _recipe_key(block, op):
    """Ops fuse iff they run the same math on the same dtype with the
    same learning rate and the same cross-parameter scalar state.
    Sparse (SelectedRows) grads group separately: their rows can't
    concatenate, and one in a group would downgrade every member to
    the per-parameter fallback at runtime."""
    param = block.var_recursive(op.desc.input("Param")[0])
    grad = block.var_recursive(op.desc.input("Grad")[0])
    shared = tuple(tuple(op.desc.input(slot))
                   for slot in _SHARED_STATE_SLOTS.get(op.type, ()))
    return (op.type,
            tuple(sorted((k, _freeze(v)) for k, v in op.desc.attrs.items())),
            tuple(op.desc.input("LearningRate")),
            shared,
            str(param.dtype),
            str(getattr(grad, "type", "")))


def fuse_update_ops(block, ops=None, min_group=2, max_numel=None):
    """Rewrite groups of same-recipe update ops in ``block`` into
    ``fused_update`` ops.  ``ops`` limits the rewrite to those Operators
    (default: every update op in the block).  Returns the Operators that
    now stand for the requested ops — fused ops plus unfused survivors —
    in block order.

    ``max_numel`` (default FLAGS_fuse_optimizer_max_numel) caps which
    parameters join a stack: kernel-launch overhead scales with op
    COUNT, which is dominated by the many tiny tensors (BN scales/
    biases, fc biases), while the stack's concat/split HBM traffic
    scales with BYTES, dominated by the few big conv/fc kernels — so
    fusing only the small ones keeps nearly all the launch win at
    negligible traffic cost.  0 means no cap."""
    if max_numel is None:
        max_numel = flags.get_flag("fuse_optimizer_max_numel")

    def small_enough(op):
        if not max_numel:
            return True
        param = block.var_recursive(op.desc.input("Param")[0])
        shape = getattr(param, "shape", None)
        if not shape or any(int(s) < 0 for s in shape):
            return True
        numel = 1
        for s in shape:
            numel *= int(s)
        return numel <= max_numel

    candidates = [op for op in (block.ops if ops is None else ops)
                  if op.type in PER_PARAM_UPDATE_OPS]
    groups = OrderedDict()
    for op in candidates:
        # capped-out ops stay in `candidates` (the returned survivors);
        # they just never join a stack
        if small_enough(op):
            groups.setdefault(_recipe_key(block, op), []).append(op)

    fused_descs = []
    for group in groups.values():
        if len(group) < min_group:
            continue
        first = group[0].desc
        # a slot is shared (learning rate, beta powers) iff every member
        # names the same vars in it; everything else stacks per-parameter
        stacked = [slot for slot in first.inputs
                   if any(op.desc.inputs.get(slot) != first.inputs[slot]
                          for op in group)]
        ins = OrderedDict()
        for slot in first.inputs:
            if slot in stacked:
                ins[slot] = [op.desc.input(slot)[0] for op in group]
            else:
                ins[slot] = list(first.inputs[slot])
        outs = OrderedDict(
            (slot, [op.desc.output(slot)[0] for op in group])
            for slot in first.outputs)
        attrs = dict(first.attrs)
        attrs["inner_type"] = first.type
        attrs["stacked_slots"] = sorted(stacked)

        member_ids = {id(op.desc) for op in group}
        insert_at = next(i for i, od in enumerate(block.desc.ops)
                         if id(od) in member_ids)
        block.desc.ops[:] = [od for od in block.desc.ops
                             if id(od) not in member_ids]
        fused = OpDesc(FUSED_UPDATE_OP, ins, outs, attrs)
        block.desc.ops.insert(insert_at, fused)
        fused_descs.append(fused)

    if fused_descs:
        block.sync_with_desc()
    mine = ({id(d) for d in fused_descs} |
            {id(op.desc) for op in candidates})
    return [op for op in block.ops if id(op.desc) in mine]


# ---------------------------------------------------------------------------
# elementwise-chain fusion (the `fuse` rewrite pass's engine)
# ---------------------------------------------------------------------------

# single-input stages: one "X" operand, one "Out" output, registered
# jittable deterministic kernels (dropout is rng, batch_norm is a
# multi-output reduction — neither belongs here)
FUSABLE_UNARY = frozenset([
    "relu", "relu6", "sigmoid", "tanh", "exp", "sqrt", "abs", "square",
    "softplus", "softsign", "leaky_relu", "elu", "brelu", "scale",
    "cast", "clip"])

# two-input stages: the chain value enters X or Y, the other operand
# rides along as a side input (bias adds, residual adds, gating muls)
FUSABLE_BINARY = frozenset([
    "elementwise_add", "elementwise_sub", "elementwise_mul",
    "elementwise_div", "elementwise_max", "elementwise_min"])


def _stage_kind(od):
    """'unary' / 'binary' when `od` can be a fused-chain stage, else
    None.  Requires exactly the canonical slots, one name each."""
    outs = od.output("Out")
    if len(outs) != 1 or outs[0] == EMPTY:
        return None
    if any(slot != "Out" and names
           for slot, names in od.outputs.items()):
        return None
    if od.type in FUSABLE_UNARY:
        want = ("X",)
    elif od.type in FUSABLE_BINARY:
        want = ("X", "Y")
    else:
        return None
    for slot in want:
        names = od.input(slot)
        if len(names) != 1 or names[0] == EMPTY:
            return None
    if any(slot not in want and names
           for slot, names in od.inputs.items()):
        return None
    return "unary" if len(want) == 1 else "binary"


def _stage_reads(od):
    return [n for n in od.input_names() if n != EMPTY]


def fuse_elemwise_chains(desc, block_idx=0, keep=(), cap=0):
    """Greedily fuse single-consumer elementwise chains in one block.

    A chain extends from stage k to the op consuming its output iff
    the intermediate has exactly one definition and one use in the
    program, is not in ``keep`` (fetches, persistables, names other
    blocks read), and the consumer is itself a fusable stage.  Every
    var any stage reads must be defined at most once in the block, so
    executing the whole chain at the LAST stage's position reads the
    same values the originals read — the rewrite is bit-identical by
    construction (the fused kernel applies the original registered
    kernels in order).

    ``cap`` bounds stages per fused op (0 = unbounded).  Chains
    shorter than 2 stages are left alone.  Returns the explain list
    (one entry per fused chain); the block is rewritten in place and
    the dead intermediate VarDescs are dropped.
    """
    bd = desc.block(block_idx)
    ops = bd.ops
    keep = set(keep)

    def_count, use_count, sole_consumer = {}, {}, {}
    for i, od in enumerate(ops):
        for n in _stage_reads(od):
            use_count[n] = use_count.get(n, 0) + 1
            sole_consumer[n] = i
        for n in od.output_names():
            if n != EMPTY:
                def_count[n] = def_count.get(n, 0) + 1

    kinds = {i: k for i, od in enumerate(ops)
             for k in [_stage_kind(od)] if k}

    def stable_reads(idx):
        # every read var must be single-def so its value at the fused
        # position (the chain's last index) matches the original read
        return all(def_count.get(n, 0) <= 1 for n in _stage_reads(ops[idx]))

    consumed = set()
    groups = []            # (chain indices, fused OpDesc)
    explain = []
    dead_names = []
    for i in range(len(ops)):
        if i in consumed or i not in kinds or not stable_reads(i):
            continue
        chain = [i]
        while True:
            if cap and len(chain) >= cap:
                break
            cur = ops[chain[-1]].output("Out")[0]
            if cur in keep or def_count.get(cur, 0) != 1 \
                    or use_count.get(cur, 0) != 1:
                break
            j = sole_consumer[cur]
            if j in consumed or j not in kinds or not stable_reads(j):
                break
            od_j = ops[j]
            if kinds[j] == "binary":
                on_x = od_j.input("X")[0] == cur
                on_y = od_j.input("Y")[0] == cur
                if on_x == on_y:  # both slots (x*x) or neither
                    break
            elif od_j.input("X")[0] != cur:
                break
            chain.append(j)
        if len(chain) < 2:
            continue

        stages = []
        side_ins = []
        for k, idx in enumerate(chain):
            od = ops[idx]
            st = {"op": od.type}
            attrs = {a: _attr_to_jsonable(v)
                     for a, v in sorted(od.attrs.items())}
            if attrs:
                st["attrs"] = attrs
            if k == 0:
                st["in"] = "X"
                side = od.input("Y")[0] if kinds[idx] == "binary" \
                    else None
            else:
                prev_out = ops[chain[k - 1]].output("Out")[0]
                if kinds[idx] == "binary":
                    if od.input("X")[0] == prev_out:
                        st["in"], side = "X", od.input("Y")[0]
                    else:
                        st["in"], side = "Y", od.input("X")[0]
                else:
                    st["in"], side = "X", None
            if side is not None:
                st["side"] = len(side_ins)
                side_ins.append(side)
            stages.append(st)

        x0 = ops[chain[0]].input("X")[0]
        final_out = ops[chain[-1]].output("Out")[0]
        ins = OrderedDict([("X", [x0])])
        if side_ins:
            ins["SideIns"] = side_ins
        fused = OpDesc(
            FUSED_ELEMWISE_OP, ins, {"Out": [final_out]},
            {"stages": json.dumps(stages, sort_keys=True),
             "inner_types": [ops[idx].type for idx in chain]})
        consumed.update(chain)
        inter = [ops[idx].output("Out")[0] for idx in chain[:-1]]
        dead_names.extend(inter)
        groups.append((chain, fused))
        explain.append({"block": block_idx,
                        "ops": [ops[idx].type for idx in chain],
                        "out": final_out, "stages": len(chain),
                        "intermediates": inter})

    if not groups:
        return []
    replace_at = {chain[-1]: fused for chain, fused in groups}
    removed = consumed - set(replace_at)
    bd.ops = [replace_at.get(i, od) for i, od in enumerate(ops)
              if i in replace_at or i not in removed]
    for n in dead_names:
        bd.vars.pop(n, None)
    return explain


def unfuse_update_ops(block):
    """Expand every ``fused_update`` in ``block`` back into its
    per-parameter ops (in stack order, at the fused op's position)."""
    if not any(od.type == FUSED_UPDATE_OP for od in block.desc.ops):
        return
    expanded = []
    for od in block.desc.ops:
        if od.type != FUSED_UPDATE_OP:
            expanded.append(od)
            continue
        stacked = set(od.attrs["stacked_slots"])
        inner_attrs = {k: v for k, v in od.attrs.items()
                       if k not in _FUSION_ATTRS}
        for i in range(len(od.input("Param"))):
            ins = {slot: ([names[i]] if slot in stacked else list(names))
                   for slot, names in od.inputs.items()}
            outs = {slot: [names[i]] for slot, names in od.outputs.items()}
            expanded.append(OpDesc(od.attrs["inner_type"], ins, outs,
                                   dict(inner_attrs)))
    block.desc.ops[:] = expanded
    block.sync_with_desc()
