"""DistributeTranspiler: pserver distribution as a graph rewrite.

TPU-native redesign of the reference pserver path (reference:
python/paddle/v2/fluid/distribute_transpiler.py:81 — params split into
blocks round-robin across pservers (split_dense_variable:39), trainer
program's optimizer ops replaced by send; pserver side applies the
optimizer per shard).  Differences by design:

  * transport is the native framed-TCP runtime (native/pserver.cc), not
    gRPC; the pserver executes optimizers in C++ (as the reference v2
    C++/Go pservers do: ParameterServer2.h:383 doOperation,
    go/pserver/optimizer.go) rather than interpreting an optimizer
    sub-block.
  * the trainer-side `dist_send` op is a host (non-jittable) op at the
    end of the block: XLA computes forward+backward on-device; the op
    ships each grad block, blocks on the sync barrier, and writes the
    refreshed parameter back — same round-trip semantics as the
    reference send+recv pair (send_op.cc:35 / recv_op.cc:86).
  * sparse SelectedRows gradients ship rows only
    (reference: getParameterSparse ParameterServer2.h:510).
"""

import numpy as np

from .. import native
from ..core.types import VarType
from ..fluid import framework
from ..ops.dist import ClientPool as _ClientPool, _bname

__all__ = ["DistributeTranspiler", "split_dense_variable", "run_pserver"]

# optimizer op type -> (native kind, attr extraction)
_OPT_MAP = {
    "sgd": native.OPT_SGD,
    "momentum": native.OPT_MOMENTUM,
    "adagrad": native.OPT_ADAGRAD,
    "adam": native.OPT_ADAM,
}


def split_dense_variable(var_list, pserver_count, min_block_size=1024,
                         max_block_size=1 << 20):
    """Split parameters into near-equal blocks to balance pserver load
    (reference: distribute_transpiler.py split_dense_variable:39).

    Returns a list of (var_name, block_id, begin, size) over flattened
    elements.
    """
    blocks = []
    for var in var_list:
        size = int(np.prod(var.shape))
        split_count = pserver_count
        if size <= min_block_size:
            split_count = 1
        block_size = (size + split_count - 1) // split_count
        if block_size < min_block_size:
            block_size = min_block_size
        block_size = min(block_size, max_block_size)
        nblocks = (size + block_size - 1) // block_size
        for i in range(nblocks):
            begin = i * block_size
            blocks.append((var.name, i, begin,
                           min(block_size, size - begin)))
    return blocks


def _validate_split_blocks(assign, params, endpoints):
    """Every parameter's send/recv blocks must tile [0, numel) exactly:
    contiguous, non-overlapping, fully covering, each on a known
    endpoint.  A custom split_method that gets this wrong would
    otherwise surface as silently-corrupted parameters after the first
    init_pservers round-trip; fail at transpile time instead, naming
    the parameter and the first bad block."""
    numel = {p.name: int(np.prod(p.shape)) for p in params}
    dropped = sorted(set(numel) - set(assign))
    if dropped:
        raise ValueError(
            "split assigned no pserver blocks to parameter(s) %s — "
            "they would silently stay at their initial values on "
            "every trainer" % dropped)
    for pname, blocks in assign.items():
        total = numel.get(pname)
        if total is None:
            raise ValueError(
                "split assigned blocks to %r, which is not a "
                "parameter being distributed" % pname)
        cursor = 0
        for ep, begin, size in sorted(blocks, key=lambda b: b[1]):
            if ep not in endpoints:
                raise ValueError(
                    "param %r block [%d:%d) is assigned to unknown "
                    "pserver endpoint %r" % (pname, begin,
                                             begin + size, ep))
            if size <= 0:
                raise ValueError(
                    "param %r has an empty/negative block at offset "
                    "%d (size %d)" % (pname, begin, size))
            if begin != cursor:
                kind = "overlaps" if begin < cursor else "leaves a gap"
                raise ValueError(
                    "param %r split %s at offset %d: block [%d:%d) "
                    "after [..:%d)" % (pname, kind, cursor, begin,
                                       begin + size, cursor))
            cursor = begin + size
        if cursor != total:
            raise ValueError(
                "param %r split covers %d of %d elements — the "
                "pserver would train a truncated parameter"
                % (pname, cursor, total))


class DistributeTranspiler:
    """reference: distribute_transpiler.py DistributeTranspiler:81."""

    def __init__(self):
        self.param_blocks = {}     # param name -> [(endpoint, begin, size)]
        self.param_opt = {}        # param name -> (kind, lr_var, attrs)
        self.trainers = 1
        self.sync = True
        self._sparse_params = set()

    # -- program rewrite ----------------------------------------------------
    def transpile(self, optimize_ops=None, params_grads=None,
                  trainer_id=0, program=None, pservers="127.0.0.1:6174",
                  trainers=1, sync=True, sync_mode=None,
                  split_method=split_dense_variable):
        """sync_mode=False selects async SGD: each trainer's gradient
        applies immediately server-side with no cross-trainer barrier
        (reference: ParameterServer2.h asyncSGD:468); pair with
        run_pserver(sync=False, async_lagged_threshold=N) to bound
        staleness (ParameterServer2.h:243).  `sync_mode` is the
        reference-style spelling; `sync` is kept as the original
        keyword — when both are given sync_mode wins."""
        if program is None:
            program = framework.default_main_program()
        self.program = program
        self.trainer_id = trainer_id
        self.trainers = trainers
        self.sync = sync if sync_mode is None else bool(sync_mode)
        endpoints = (pservers.split(",") if isinstance(pservers, str)
                     else list(pservers))
        self.endpoints = endpoints

        block = program.global_block()
        params = [p for p, g in params_grads]
        grads = {p.name: g for p, g in params_grads}

        # per-param optimizer config from the optimize ops being removed
        opt_ops = [op for op in block.ops if op.type in _OPT_MAP]
        configured = {}
        for op in opt_ops:
            if op.type not in _OPT_MAP:
                continue
            pname = op.desc.input("Param")[0]
            attrs = dict(op.desc.attrs)
            lr_name = op.desc.input("LearningRate")[0]
            if op.type == "momentum":
                hp = (float(attrs.get("mu", 0.9)), 0.0, 0.0)
            elif op.type == "adagrad":
                hp = (float(attrs.get("epsilon", 1e-6)), 0.0, 0.0)
            elif op.type == "adam":
                hp = (float(attrs.get("beta1", 0.9)),
                      float(attrs.get("beta2", 0.999)),
                      float(attrs.get("epsilon", 1e-8)))
            else:
                hp = (0.0, 0.0, 0.0)
            configured[pname] = (_OPT_MAP[op.type], lr_name, hp)
        unsupported = [p.name for p in params if p.name not in configured]
        if unsupported:
            raise NotImplementedError(
                "pserver-side optimizer supports sgd/momentum/adagrad/"
                "adam; no config found for params %s" % unsupported)
        self.param_opt = configured

        # pserver optimizer config snapshots the LR once at
        # init_pservers; an LR-decay schedule writing the LR var in the
        # trainer program would silently have no effect on updates
        # (the reference ships the current LR with every update —
        # ParameterServer2 trainingConfig). Surface that loudly.
        lr_names = {lr for _k, lr, _hp in configured.values()}
        written = {}
        for op in block.ops:
            if op in opt_ops:
                continue
            for outs in op.desc.outputs.values():
                for o in outs:
                    written.setdefault(o, []).append(op)
        def _is_static_lr_writer(op):
            # Constant producers (fill_constant LR vars, the per-param
            # `scale` that Optimizer._create_param_lr emits) yield the
            # same value every step — not a schedule. Warn only when
            # the writer updates one of its own inputs in place or its
            # inputs are produced by other ops (step counters).
            in_names = [i for ins in op.desc.inputs.values() for i in ins]
            out_names = [o for outs in op.desc.outputs.values()
                         for o in outs]
            if any(o in in_names for o in out_names):
                return False  # in-place update: evolves across steps

            def _static_src(n):
                # produced by no op AND persistable (a param/constant);
                # a non-persistable producer-less var is a feed — dynamic
                if written.get(n):
                    return False
                v = block.vars.get(n)
                return v is not None and bool(
                    getattr(v, "persistable", False))

            return all(_static_src(i) for i in in_names)
        decay_writers = [
            op.type for name in lr_names for op in written.get(name, [])
            if not _is_static_lr_writer(op)]
        if decay_writers:
            import warnings

            warnings.warn(
                "DistributeTranspiler: ops %s write the learning-rate "
                "var, but the pserver optimizer snapshots LR once at "
                "init_pservers(); the decay schedule will NOT affect "
                "pserver updates. Re-run init_pservers() to refresh, "
                "or keep the optimizer local." % sorted(set(decay_writers)),
                stacklevel=2)

        # sparse-grad params stay whole on one endpoint (rows route to a
        # single owner; reference sparse tables also shard by row
        # server-set, not by flat range)
        sparse = {p.name for p in params
                  if grads[p.name].type == VarType.SELECTED_ROWS}
        self._sparse_params = sparse

        # param -> blocks -> endpoints, round-robin over block list
        # (reference: round_robin distributed_spliter.py)
        dense_params = [p for p in params if p.name not in sparse]
        blocks = split_method(dense_params, len(endpoints))
        assign = {}
        for i, (pname, _bid, begin, size) in enumerate(blocks):
            assign.setdefault(pname, []).append(
                (endpoints[i % len(endpoints)], begin, size))
        for j, p in enumerate(p for p in params if p.name in sparse):
            assign[p.name] = [(endpoints[j % len(endpoints)], 0,
                               int(np.prod(p.shape)))]
        # a bad split_method here means every trainer ships wrong byte
        # ranges to every pserver — validate the tiling NOW, before
        # the rewrite lands in the program
        _validate_split_blocks(assign, params, set(endpoints))
        self.param_blocks = assign

        # drop the optimizer ops (+ their lr decay helpers stay; they're
        # harmless) and append one dist_send per param
        keep = [op for op in block.ops if op not in opt_ops]
        removed_descs = {id(op.desc) for op in opt_ops}
        block.ops = keep
        block.desc.ops = [d for d in block.desc.ops
                          if id(d) not in removed_descs]

        for p in params:
            g = grads[p.name]
            block.append_op(
                type="dist_send",
                inputs={"Param": [p], "Grad": [g]},
                outputs={"ParamOut": [p]},
                attrs={
                    "param_name": p.name,
                    "blocks": [(ep, int(b), int(s))
                               for ep, b, s in assign[p.name]],
                }, infer_shape=False)

        # the rewritten program ships to a whole cluster: verify its
        # structure NOW (cheap desc walk, docs/ANALYSIS.md) so a
        # transpiler bug fails at transpile time with op/var identity,
        # not as an opaque error on some remote trainer
        from .. import analysis

        analysis.verify_program(program, level="structural") \
            .publish(origin="transpiler").raise_on_error()
        return self

    # -- runtime helpers ----------------------------------------------------
    def init_pservers(self, scope=None):
        """Push initial parameter blocks + optimizer config to their
        pservers (first trainer wins server-side), then pull the
        canonical values so all trainers start identical."""
        from ..core import scope as scope_mod

        scope = scope or scope_mod.global_scope()
        for pname, blocks in self.param_blocks.items():
            kind, lr_name, hp = self.param_opt[pname]
            lr_val = scope.get(lr_name)
            lr = float(np.asarray(lr_val).reshape(-1)[0]) \
                if lr_val is not None else 0.01
            flat = np.asarray(scope.get(pname)).reshape(-1)
            for ep, begin, size in blocks:
                c = _ClientPool.get(ep)
                c.init_param(_bname(pname, begin), flat[begin:begin + size],
                             opt_kind=kind, lr=lr, hp1=hp[0], hp2=hp[1],
                             hp3=hp[2])
            # pull canonical init
            out = np.empty_like(flat)
            for ep, begin, size in blocks:
                out[begin:begin + size] = _ClientPool.get(ep).get_param(
                    _bname(pname, begin), size)
            shaped = out.reshape(np.asarray(scope.get(pname)).shape)
            scope.set(pname, shaped)

    def release(self):
        _ClientPool.reset()


def _bname(pname, begin):
    return "%s@%d" % (pname, begin)


def run_pserver(endpoint="127.0.0.1:6174", trainers=1, sync=True,
                async_lagged_threshold=0):
    """Start a pserver for `endpoint` and return the server object
    (reference: the pserver startup path of recv_op/ListenAndServ and
    paddle_pserver2 main).  sync=False serves the async-SGD path;
    async_lagged_threshold > 0 discards gradients computed against
    parameters at least that many versions old (reference:
    ParameterServer2.h:243 staleness control).  Blocks only in
    __main__ usage; tests call .stop()."""
    host, port = endpoint.rsplit(":", 1)
    return native.ParameterServer(
        port=int(port), num_trainers=trainers, sync=sync,
        async_lagged_threshold=async_lagged_threshold)
