"""Symbolic backward pass over the program IR.

TPU-native re-design of the reference autodiff builders
(reference: python/paddle/v2/fluid/backward.py:338 append_backward,
:116 _addup_repetitive_outputs_, :166 _remove_no_grad_branch_;
C++ twin paddle/framework/backward.cc:523 AppendBackward).

Matches the reference's *structure* — grad ops are appended to the same
block, gradient variables are named `<var>@GRAD`, repeated contributions
are accumulated with `sum` ops — but each grad op's kernel is derived from
the forward kernel with jax.vjp (see ops/registry.py), so no per-op grad
functor library exists.  Control-flow ops (scan/cond based) differentiate
through the same mechanism, replacing the reference's recursive sub-block
backward (backward.cc:415 MakeBlockBackward).
"""

from collections import defaultdict

from ..core.desc import OpDesc
from ..core.types import grad_var_name, GRAD_SUFFIX
from ..obs import trace as obs_trace
from ..ops import registry as op_registry
from . import framework

__all__ = ["append_backward", "calc_gradient"]

EMPTY = "@EMPTY@"


def _op_info_for(op_type):
    return op_registry.get_op_info(op_type)


class _GradState:
    def __init__(self, block):
        self.block = block
        self.contribs = defaultdict(list)  # var name -> [grad contrib names]
        self.new_ops = []

    def add_contrib(self, var_name):
        """Reserve a fresh grad contribution name for var_name."""
        n = len(self.contribs[var_name])
        gname = (grad_var_name(var_name) if n == 0
                 else "%s@RENAME@%d" % (grad_var_name(var_name), n))
        self.contribs[var_name].append(gname)
        return gname

    def has_grad(self, var_name):
        return len(self.contribs[var_name]) > 0

    def finalize(self, var_name):
        """Return the final grad var name for var_name, emitting a `sum` op
        if there are multiple contributions (reference:
        backward.py:116 _addup_repetitive_outputs_)."""
        contribs = self.contribs[var_name]
        if not contribs:
            return None
        if len(contribs) == 1:
            return contribs[0]
        out = grad_var_name(var_name)
        if out in contribs:
            # rename the canonical one so sum's output is fresh
            renamed = out + "@RENAME@0r"
            for op in self.new_ops:
                for names in op.outputs.values():
                    for i, n in enumerate(names):
                        if n == out:
                            names[i] = renamed
                for names in op.inputs.values():
                    for i, n in enumerate(names):
                        if n == out:
                            names[i] = renamed
            contribs = [renamed if c == out else c for c in contribs]
        sum_op = OpDesc("sum", {"X": contribs}, {"Out": [out]}, {})
        self.new_ops.append(sum_op)
        self.contribs[var_name] = [out]
        return out


def _make_grad_op(op_desc, state, no_grad_names):
    """Build the grad OpDesc for one forward op; returns None if no input
    needs a gradient."""
    info = _op_info_for(op_desc.type)
    if info.stop_gradient_op:
        return None

    # out grads (finalize accumulations from already-emitted consumers)
    og_inputs = {}
    any_og = False
    for slot, names in op_desc.outputs.items():
        gs = []
        for n in names:
            g = state.finalize(n) if n != EMPTY else None
            gs.append(g if g is not None else EMPTY)
            any_og = any_og or g is not None
        og_inputs["OG@" + slot] = gs
    if not any_og:
        return None

    # which inputs get grads
    out_slots = {}
    any_grad = False
    for slot, names in op_desc.inputs.items():
        if slot in info.nondiff_inputs:
            continue
        outs = []
        for n in names:
            if n in no_grad_names:
                outs.append(EMPTY)
            else:
                outs.append(state.add_contrib(n))
                any_grad = True
        out_slots[slot + GRAD_SUFFIX] = outs
    if not any_grad:
        return None

    grad_inputs = dict(op_desc.inputs)
    for slot, names in op_desc.outputs.items():
        grad_inputs["O@" + slot] = list(names)
    grad_inputs.update(og_inputs)

    return OpDesc(op_desc.type + "_grad", grad_inputs, out_slots,
                  dict(op_desc.attrs))


def _collect_no_grad(block, no_grad_set):
    no_grad = set(no_grad_set or ())
    bd = block.desc
    prog = block.program.desc
    while True:
        for name, vd in bd.vars.items():
            if vd.stop_gradient:
                no_grad.add(name)
        if bd.parent_idx < 0:
            break
        bd = prog.block(bd.parent_idx)
    return no_grad


def _append_grad_ops(block, targets, target_grads, no_grad_names,
                     stop_at_op=None):
    """Emit grad ops into `block` for the reverse slice from `targets`.
    targets: list of var names seeded with grads named by target_grads."""
    state = _GradState(block)
    for t, tg in zip(targets, target_grads):
        state.contribs[t].append(tg)

    fwd_ops = list(block.desc.ops)
    for op_desc in reversed(fwd_ops):
        if op_registry.is_grad_op_type(op_desc.type):
            continue
        info = _op_info_for(op_desc.type)
        if info.stop_gradient_op:
            continue
        if not any(state.has_grad(n) for n in op_desc.output_names()):
            continue
        g = _make_grad_op(op_desc, state, no_grad_names)
        if g is not None:
            state.new_ops.append(g)

    return state


def append_backward(loss, parameter_list=None, no_grad_set=None,
                    callbacks=None):
    """Append backward ops computing d(loss)/d(param) for every trainable
    parameter; returns [(param, grad_var)] (reference: backward.py:338).
    """
    assert isinstance(loss, framework.Variable)
    program = loss.block.program
    block = program.global_block()
    with obs_trace.span("startup/program_backward", cat=obs_trace.STARTUP,
                        ops_before=len(block.desc.ops)) as appended:
        params_grads = _append_backward(loss, program, block,
                                        parameter_list, no_grad_set,
                                        callbacks)
        appended.set(ops_after=len(block.desc.ops))
    return params_grads


def _append_backward(loss, program, block, parameter_list, no_grad_set,
                     callbacks):
    no_grad_names = _collect_no_grad(block, no_grad_set)

    # seed: d loss / d loss = 1 (reference fills with fill_constant)
    loss_grad = grad_var_name(loss.name)
    seed_op = OpDesc(
        "fill_constant", {}, {"Out": [loss_grad]},
        {"shape": list(loss.shape) or [1], "value": 1.0,
         "dtype": loss.dtype})
    block.desc.ops.append(seed_op)
    _ensure_grad_var(block, loss.name)

    state = _append_grad_ops(block, [loss.name], [loss_grad],
                             no_grad_names)

    # finalize leaf grads (params & inputs) — emits pending sum ops
    params = block.all_parameters()
    if parameter_list is not None:
        wanted = set(parameter_list)
        params = [p for p in params if p.name in wanted]
    params_grads = []
    shared_uses = 0
    for p in params:
        if not getattr(p, "trainable", True):
            continue
        uses = len(state.contribs[p.name])
        if uses > 1:
            # a weight read by several ops (shared across depth): its
            # gradient is the `sum` of one contribution per use
            shared_uses += uses
        gname = state.finalize(p.name)
        if gname is None:
            continue
        params_grads.append((p, gname))

    if shared_uses:
        from ..obs import telemetry

        telemetry.on_shared_parameter_uses(program, shared_uses)
    if callbacks is None:
        callbacks = [_error_clip_callback]
    elif not isinstance(callbacks, (list, tuple)):
        callbacks = [callbacks]
    for op in state.new_ops:
        block.desc.ops.append(op)
        for names in op.outputs.values():
            for n in names:
                if n != EMPTY:
                    _ensure_grad_var(block, _src_of(n))
        _apply_sparse_grad_types(block, op)
        # per-appended-grad-op hook (reference: backward.py callbacks;
        # error_clip ops are injected right after the grad op)
        for cb in callbacks:
            cb(block=block, context={})
    block.sync_with_desc()

    # return Variables for the grads
    out = []
    for p, gname in params_grads:
        gvar = block.var(gname) if block.has_var(gname) else None
        out.append((p, gvar))
    return out


def calc_gradient(targets, inputs, target_gradients=None, no_grad_set=None):
    """Compute grads of targets w.r.t. inputs (reference later adds
    gradients.calc_gradient; provided for API completeness)."""
    if not isinstance(targets, (list, tuple)):
        targets = [targets]
    if not isinstance(inputs, (list, tuple)):
        inputs = [inputs]
    block = targets[0].block
    program = block.program
    no_grad_names = _collect_no_grad(block, no_grad_set)
    # inputs must receive grads even if marked stop_gradient
    no_grad_names -= {v.name for v in inputs}

    tnames, tgrads = [], []
    for i, t in enumerate(targets):
        g = grad_var_name(t.name)
        if target_gradients is not None and target_gradients[i] is not None:
            g = target_gradients[i].name
        else:
            block.desc.ops.append(OpDesc(
                "fill_constant", {}, {"Out": [g]},
                {"shape": list(t.shape) or [1], "value": 1.0,
                 "dtype": t.dtype}))
            _ensure_grad_var(block, t.name)
        tnames.append(t.name)
        tgrads.append(g)

    state = _append_grad_ops(block, tnames, tgrads, no_grad_names)
    grads = []
    for v in inputs:
        grads.append(state.finalize(v.name))
    block.desc.ops.extend(state.new_ops)
    for op in state.new_ops:
        for names in op.outputs.values():
            for n in names:
                if n != EMPTY:
                    _ensure_grad_var(block, _src_of(n))
        _apply_sparse_grad_types(block, op)
    block.sync_with_desc()
    return [block.var(g) if g is not None else None for g in grads]


def _error_clip_callback(block, context):
    """Apply per-variable error clipping to the grad op just appended
    (reference: clip.py error_clip_callback)."""
    op_desc = block.desc.ops[-1]
    for grad_n in op_desc.output_names():
        if grad_n == EMPTY or not grad_n.endswith(GRAD_SUFFIX):
            continue
        fwd_name = _src_of(grad_n)
        try:
            fwd_var = block.var_recursive(fwd_name)
        except ValueError:
            continue
        error_clip = getattr(fwd_var, "error_clip", None)
        if error_clip is not None:
            error_clip.append_clip_op(block, grad_n)


def _src_of(grad_name):
    base = grad_name.split("@RENAME@")[0]
    if base.endswith(GRAD_SUFFIX):
        return base[: -len(GRAD_SUFFIX)]
    return base


def _apply_sparse_grad_types(block, op_desc):
    """Type grad VarDescs that a grad op produces as SelectedRows (the
    descs default to mirroring the dense forward var).  Driven by the
    forward op's registry hook — reference: the per-op VarTypeInference
    pass, e.g. lookup_table_op.cc marking W@GRAD as SelectedRows when
    is_sparse.  Grad-accumulation `sum` ops propagate the typing: the
    sum of all-SelectedRows contributions is a SelectedRows (rows
    concatenated, reference: sum_op.cc SelectedRows path), so a table
    looked up more than once still routes sparse."""
    from ..core.types import VarType

    if op_desc.type == "sum":
        in_names = [n for n in op_desc.input("X") if n != EMPTY]
        in_descs = [block.desc.vars.get(n) for n in in_names]
        if in_descs and all(
                vd is not None and vd.type == VarType.SELECTED_ROWS
                for vd in in_descs):
            for n in op_desc.output("Out"):
                vd = block.desc.vars.get(n)
                if vd is not None:
                    vd.type = VarType.SELECTED_ROWS
        return
    if not op_registry.is_grad_op_type(op_desc.type):
        return
    info = _op_info_for(op_registry.forward_type_of_grad(op_desc.type))
    hook = info.sparse_grad_slots
    if hook is None:
        return
    for slot in hook(op_desc.attrs):
        for n in op_desc.outputs.get(slot + GRAD_SUFFIX, []):
            if n == EMPTY:
                continue
            vd = block.desc.vars.get(n)
            if vd is not None:
                vd.type = VarType.SELECTED_ROWS


def _ensure_grad_var(block, src_name):
    """Create VarDescs for `src@GRAD` (+ any renames) mirroring src meta."""
    from ..core.desc import VarDesc

    bd = block.desc
    src = None
    b = bd
    prog = block.program.desc
    while True:
        if src_name in b.vars:
            src = b.vars[src_name]
            break
        if b.parent_idx < 0:
            break
        b = prog.block(b.parent_idx)
    gname = grad_var_name(src_name)
    names = [gname]
    # include rename variants already referenced by ops
    for op in bd.ops:
        for ns in list(op.outputs.values()) + list(op.inputs.values()):
            for n in ns:
                if n.startswith(gname + "@RENAME@"):
                    names.append(n)
    for n in names:
        if n not in bd.vars:
            vd = VarDesc(n)
            if src is not None:
                vd.type = src.type
                vd.dtype = src.dtype
                vd.shape = src.shape
                vd.lod_level = src.lod_level
            bd.vars[n] = vd
