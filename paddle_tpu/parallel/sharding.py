"""Sharding specs for program state and feeds.

The reference decides placement imperatively (scatter params to device
threads, MultiGradientMachine.h:100-140; split LoDTensor across places,
parallel_do_op.cc:37-47).  Here placement is declarative: every buffer
gets a NamedSharding over the mesh and XLA GSPMD partitions the program.
"""

import re as _re

import jax
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from ..ops.optimizer_ops import UPDATE_OPS

__all__ = ["param_spec", "param_spec_reason", "batch_spec", "replicated",
           "shard_state", "shard_feeds", "zero1_spec",
           "zero1_spec_reason"]


def replicated(mesh):
    return NamedSharding(mesh, P())


def param_spec_reason(name, shape, mesh, mp_axis="mp", min_shard_dim=512):
    """(spec, reason) for a parameter under the default tensor-parallel
    layout.  `reason` is None when the spec shards (or replication is
    deliberate policy: no mp axis, or a non-2-D tensor the conv policy
    replicates on purpose); otherwise it is a sentence explaining what
    FORCED replication (min_shard_dim or divisibility) — the sharding
    analyzer's S001 cites it instead of letting the fallback stay
    silent."""
    if mp_axis not in mesh.shape:
        return P(), None
    mp = mesh.shape[mp_axis]
    if mp == 1:
        return P(), None
    if len(shape) != 2:
        return P(), None  # conv filters / biases / stats: policy
    rows, cols = int(shape[0]), int(shape[1])
    # embedding / big row-major tables: shard rows
    if rows >= min_shard_dim * mp and rows % mp == 0 and rows >= cols:
        return P(mp_axis, None), None
    if cols % mp == 0 and cols >= min_shard_dim:
        return P(None, mp_axis), None
    if rows % mp == 0 and rows >= min_shard_dim:
        return P(mp_axis, None), None
    if max(rows, cols) < min_shard_dim:
        reason = ("both dims of (%d, %d) are below min_shard_dim %d"
                  % (rows, cols, min_shard_dim))
    elif cols >= min_shard_dim and cols % mp:
        reason = ("cols %d not divisible by %s=%d (rows %d %s)"
                  % (cols, mp_axis, mp,
                     rows, "not divisible either" if rows % mp
                     else "below min_shard_dim %d" % min_shard_dim))
    else:
        reason = ("rows %d not divisible by %s=%d and cols %d below "
                  "min_shard_dim %d" % (rows, mp_axis, mp, cols,
                                        min_shard_dim))
    return P(), reason


def param_spec(name, shape, mesh, mp_axis="mp", min_shard_dim=512):
    """Default tensor-parallel layout for a parameter.

    Large 2-D weights (fc/projection) shard their output dim over mp;
    large embedding tables shard the vocab dim over mp (row-sharded like
    the reference's blockwise pserver partitioning,
    reference: pserver/ParameterServer2.h:73, distribute_transpiler.py:39);
    everything else (conv filters, biases, BN stats) is replicated — conv
    weights are small relative to activations, and replication keeps the
    conv spatially partitionable by dp.  See `param_spec_reason` for the
    variant that also says WHY a tensor fell back to replication.
    """
    spec, _reason = param_spec_reason(name, shape, mesh, mp_axis=mp_axis,
                                      min_shard_dim=min_shard_dim)
    return spec


def batch_spec(shape, mesh, dp_axis="dp"):
    """Feeds shard their leading (batch) dim over dp."""
    if dp_axis not in mesh.shape or len(shape) == 0:
        return P()
    return P(dp_axis)


def shard_state(state, mesh, var_shapes=None, mp_axis="mp"):
    """Return {name: NamedSharding} for a state dict (arrays or abstract)."""
    specs = {}
    for name, v in state.items():
        shape = v.shape if hasattr(v, "shape") else var_shapes[name]
        specs[name] = NamedSharding(mesh, param_spec(name, shape, mesh,
                                                     mp_axis=mp_axis))
    return specs


def shard_feeds(feeds, mesh, dp_axis="dp"):
    specs = {}
    for name, v in feeds.items():
        specs[name] = NamedSharding(mesh, batch_spec(v.shape, mesh,
                                                     dp_axis=dp_axis))
    return specs


# optimizer accumulator vars are named {param}_{acc}_{N} by
# fluid/optimizer.py _add_accumulator; these are the acc strings of the
# 11 optimizers
_ACC_NAME = _re.compile(
    r"_(velocity|moment[12]?|inf_norm|avg_squared_grad|"
    r"avg_squared_update|mean_square|squared|linear)_\d+$")

# optimizer-op input slots that are NOT accumulator state
_NON_STATE_SLOTS = frozenset(["Param", "Grad", "LearningRate"])


def optimizer_state_names(program):
    """The exact accumulator var names of a built program: every input
    to an optimizer op except Param/Grad/LearningRate.  Exact where the
    name-suffix regex is a guess (a user var named '*_squared_3' would
    fool the regex but can never appear in an optimizer slot)."""
    names = set()
    for block in program.blocks:
        for op in block.ops:
            if op.type not in UPDATE_OPS:
                continue
            for slot, vars_ in op.desc.inputs.items():
                if slot not in _NON_STATE_SLOTS:
                    names.update(vars_)
    return names


def is_optimizer_state(name, known=None):
    """`known` (from optimizer_state_names) is authoritative; the name
    regex is the fallback for detached state dicts with no program."""
    if known is not None:
        return name in known
    return bool(_ACC_NAME.search(name))


def zero1_spec_reason(base_spec, shape, mesh, dp_axis="dp"):
    """(spec, reason) for the ZeRO-1 layout of an optimizer-state
    tensor.  `reason` is None when a dim sharded (or there is no dp
    axis to shard over); otherwise it says why every dim stayed whole —
    the S001 citation for optimizer state that silently keeps dp full
    copies."""
    if dp_axis not in mesh.shape or mesh.shape[dp_axis] == 1:
        return base_spec, None
    dp = mesh.shape[dp_axis]
    dims = list(base_spec) + [None] * (len(shape) - len(base_spec))
    for i, (d, s) in enumerate(zip(dims, shape)):
        if d is None and int(s) % dp == 0 and int(s) >= dp:
            dims[i] = dp_axis
            return P(*dims), None
    if not shape:
        reason = "scalar state cannot shard over %s=%d" % (dp_axis, dp)
    else:
        reason = ("no free dim of %s divides %s=%d (zero-1 keeps %d "
                  "full copies)" % (tuple(int(s) for s in shape),
                                    dp_axis, dp, dp))
    return base_spec, reason


def zero1_spec(base_spec, shape, mesh, dp_axis="dp"):
    """ZeRO-1: shard an optimizer-state tensor over the dp axis on its
    first free, divisible dim (on top of any mp sharding the matching
    parameter has).  GSPMD then reduce-scatters the gradient into the
    shard-wise accumulator update and all-gathers the updated params —
    all-reduce bandwidth, 1/dp optimizer-state memory.  See
    `zero1_spec_reason` for the variant that reports why a tensor could
    not shard."""
    spec, _reason = zero1_spec_reason(base_spec, shape, mesh,
                                      dp_axis=dp_axis)
    return spec


def shard_map_norep(fn, **kwargs):
    """shard_map with replication checking off — the one spelling the
    ring / pipeline / moe / spmd modules share."""
    return shard_map(fn, check_vma=False, **kwargs)
