"""Model save/load and inference export.

reference: python/paddle/v2/fluid/io.py (save_vars:63,
save_persistables:112, load_persistables:174, save_inference_model:237,
load_inference_model:325).  Variables serialize as .npz files (one per
var, same one-file-per-var layout as the reference's save_op), the program
as its canonical JSON IR string.
"""

import os
import json

import numpy as np

from . import framework
from .framework import Program, Parameter, Variable, default_main_program
from ..core.scope import global_scope
from ..core.ragged import RaggedTensor
from ..obs import trace as obs_trace

__all__ = [
    "save_vars", "save_params", "save_persistables", "load_vars",
    "load_params", "load_persistables", "save_inference_model",
    "load_inference_model", "get_inference_program",
]


def is_parameter(var):
    return isinstance(var, Parameter) or getattr(var.desc, "is_parameter",
                                                 False)


def is_persistable(var):
    return var.persistable


def _host_values(value):
    """bf16 is an internal compute dtype (a startup program run under
    AMP leaves its outputs in it) that numpy's file formats cannot
    name — it comes back as raw `|V2` — so files hold f32, like every
    fetch (Executor._to_numpy)."""
    arr = np.asarray(value)
    return arr.astype(np.float32) if arr.dtype.name == "bfloat16" else arr


def _save_one(dirname, name, value):
    path = os.path.join(dirname, name.replace("/", "_"))
    if isinstance(value, RaggedTensor):
        np.savez(path, __ragged__=1, values=_host_values(value.values),
                 nvalid=np.asarray(value.nvalid),
                 **{"rs%d" % i: np.asarray(rs)
                    for i, rs in enumerate(value.row_splits)})
    else:
        np.savez(path, __ragged__=0, values=_host_values(value))


def _load_one(dirname, name, missing_ok=False, fileobj=None):
    """fileobj: already-open file-like holding the npz bytes (lets a
    caller that just read the file for a CRC pass decode the same
    buffer instead of re-reading disk — see fluid/checkpoint.py)."""
    if fileobj is None:
        path = os.path.join(dirname, name.replace("/", "_") + ".npz")
        if not os.path.exists(path):
            if missing_ok:
                return None
            raise IOError("no saved var %r under %s" % (name, dirname))
        fileobj = path
    with np.load(fileobj) as data:
        if int(data["__ragged__"]) == 1:
            splits = []
            i = 0
            while "rs%d" % i in data:
                splits.append(data["rs%d" % i])
                i += 1
            import jax.numpy as jnp

            return RaggedTensor(jnp.asarray(data["values"]), splits,
                                nvalid=int(data["nvalid"]))
        return data["values"].copy()


def save_vars(executor, dirname, main_program=None, vars=None,
              predicate=None, save_file_name=None):
    """reference: io.py:63."""
    if vars is None:
        if main_program is None:
            main_program = default_main_program()
        vars = list(filter(predicate, main_program.list_vars()))
    os.makedirs(dirname, exist_ok=True)
    scope = global_scope()
    for var in vars:
        if isinstance(var, Variable):
            name = var.name
        else:
            name = str(var)
        val = scope.get(name)
        if val is None:
            continue
        _save_one(dirname, name, val)


def save_params(executor, dirname, main_program=None):
    save_vars(executor, dirname, main_program, predicate=is_parameter)


def save_persistables(executor, dirname, main_program=None):
    """reference: io.py:112."""
    save_vars(executor, dirname, main_program, predicate=is_persistable)


def load_vars(executor, dirname, main_program=None, vars=None,
              predicate=None):
    """reference: io.py load_vars."""
    if vars is None:
        if main_program is None:
            main_program = default_main_program()
        vars = list(filter(predicate, main_program.list_vars()))
    scope = global_scope()
    import jax

    device = executor.place.device() if executor is not None else None
    # the restart path of a server or a resumed job: the files' way from
    # the disk to the device is a part of start-up
    with obs_trace.span("startup/load", cat=obs_trace.STARTUP) as loaded:
        files = nbytes = 0
        for var in vars:
            name = var.name if isinstance(var, Variable) else str(var)
            # vars that had no value at save time were skipped there;
            # mirror that instead of failing the round-trip
            val = _load_one(dirname, name, missing_ok=True)
            if val is None:
                continue
            files += 1
            if isinstance(val, np.ndarray):
                nbytes += val.nbytes
                if device is not None:
                    val = jax.device_put(val, device)
            scope.set_local(name, val)
        loaded.set(files=files, bytes=nbytes)


def load_params(executor, dirname, main_program=None):
    load_vars(executor, dirname, main_program, predicate=is_parameter)


def load_persistables(executor, dirname, main_program=None):
    """reference: io.py:174."""
    load_vars(executor, dirname, main_program, predicate=is_persistable)


def get_inference_program(target_vars, main_program=None):
    if main_program is None:
        main_program = default_main_program()
    if not isinstance(target_vars, list):
        target_vars = [target_vars]
    return prune_program(main_program, target_vars)


def _op_block_refs(op):
    """Sub-block indices referenced from an op's attrs."""
    from ..core.desc import BlockRef

    refs = []
    for v in op.attrs.values():
        if isinstance(v, BlockRef):
            refs.append(v.idx)
        elif isinstance(v, (list, tuple)):
            refs.extend(x.idx for x in v if isinstance(x, BlockRef))
    return refs


def _closure_reads(desc, block_idx, memo):
    """Every name a block tree reads before writing it — the closure a
    parent must keep alive when it keeps the owning op.  Control-flow
    builders list closures in op inputs already; this recursion is the
    safety net for any op that doesn't."""
    if block_idx in memo:
        return memo[block_idx]
    bd = desc.block(block_idx)
    reads, writes = set(), set()
    for op in bd.ops:
        for n in op.input_names():
            if n != "@EMPTY@" and n not in writes:
                reads.add(n)
        for sub in _op_block_refs(op):
            reads |= (_closure_reads(desc, sub, memo) - writes)
        writes.update(op.output_names())
    memo[block_idx] = {n for n in reads if n not in bd.vars}
    return memo[block_idx]


def prune_program(program, targets):
    """Prune block-0 ops not needed for `targets`; a kept op keeps its
    whole sub-block tree alive, including closure vars the sub-blocks
    read from outer scope (reference: framework/prune.cc:108 recursing
    the same way)."""
    target_names = {t.name if isinstance(t, Variable) else str(t)
                    for t in targets}
    pruned = program.clone(for_test=True)
    desc = pruned.desc
    block = desc.block(0)
    needed = set(target_names)
    produced = set()
    memo = {}
    keep = []
    for op in reversed(block.ops):
        if any(n in needed for n in op.output_names()):
            keep.append(op)
            needed.update(n for n in op.input_names() if n != "@EMPTY@")
            produced.update(op.output_names())
            for sub in _op_block_refs(op):
                needed |= _closure_reads(desc, sub, memo)
    block.ops = list(reversed(keep))
    pruned.blocks[0].sync_with_desc()

    # every target must be reachable in the pruned block-0 graph — a
    # target living only inside a sub-block would otherwise export an
    # empty program that fails much later, at inference time
    for name in target_names:
        if name in produced:
            continue
        if block.has_var(name) and block.vars[name].persistable:
            continue  # parameters are valid targets without an op
        if not block.has_var(name):
            raise ValueError(
                "inference target %r is not a block-0 variable; fetch "
                "a block-0 output (e.g. the recurrent group's result, "
                "not a variable inside its step block)" % name)
        raise ValueError(
            "inference target %r is produced by no op (feed "
            "variables cannot be targets)" % name)

    # drop root VarDescs nothing in the pruned graph references:
    # without this every @GRAD/@RENAME temp of the training tail ships
    # as declaration debris in the export (the analyzer's L005/D002
    # findings — found by dogfooding proglint on our own exports).
    # Persistables stay (load_inference_model loads by predicate), as
    # does anything a sub-block touches by name.
    referenced = set(target_names)
    for b in desc.blocks:
        for op in b.ops:
            referenced.update(op.input_names())
            referenced.update(op.output_names())
        if b.idx != 0:
            referenced.update(b.vars.keys())
    for name in list(block.vars):
        if name in referenced or block.vars[name].persistable:
            continue
        del block.vars[name]
        pruned.blocks[0].vars.pop(name, None)
    return pruned


def _feed_meta(program, feed_names):
    """Shape/dtype/lod metadata for each feed var — what an online
    server needs to synthesize warmup batches and validate request
    payloads without rebuilding the topology (see serving/engine.py)."""
    from ..core.types import np_dtype

    block = program.global_block()
    meta = {}
    for name in feed_names:
        var = block.var(name)
        dtype = (np.dtype(np_dtype(var.dtype)).name
                 if var.dtype is not None else None)
        meta[name] = {"shape": list(var.shape), "dtype": dtype,
                      "lod_level": var.lod_level}
    return meta


def save_inference_model(dirname, feeded_var_names, target_vars, executor,
                         main_program=None, model_filename="__model__",
                         bucket_hints=None):
    """reference: io.py:237 — writes the pruned inference ProgramDesc plus
    all persistable params.

    `bucket_hints` (optional dict, e.g. ``{"batch_buckets": [1, 8, 32],
    "token_bucket": 64}``) records the shape buckets the exporter
    expects to serve under; `serving.InferenceEngine.from_saved_model`
    seeds its compile-cache config from them."""
    if main_program is None:
        main_program = default_main_program()
    if isinstance(feeded_var_names, str):
        feeded_var_names = [feeded_var_names]
    if not isinstance(target_vars, list):
        target_vars = [target_vars]
    os.makedirs(dirname, exist_ok=True)

    pruned = prune_program(main_program, target_vars)
    meta = {
        "program": pruned.desc.to_dict(),
        "feed_names": list(feeded_var_names),
        "fetch_names": [t.name if isinstance(t, Variable) else str(t)
                        for t in target_vars],
        "feed_meta": _feed_meta(main_program, feeded_var_names),
    }
    if bucket_hints is not None:
        meta["bucket_hints"] = dict(bucket_hints)
    with open(os.path.join(dirname, model_filename), "w") as f:
        json.dump(meta, f)
    save_persistables(executor, dirname, main_program)
    return pruned


def load_inference_model(dirname, executor, model_filename="__model__",
                         return_meta=False):
    """reference: io.py:325 — returns (program, feed_names, fetch_vars);
    with `return_meta`, appends the raw export metadata dict
    (feed_meta/bucket_hints) as a fourth element."""
    with obs_trace.span("startup/load", cat=obs_trace.STARTUP,
                        program=model_filename):
        return _load_inference_model(dirname, executor, model_filename,
                                     return_meta)


def _load_inference_model(dirname, executor, model_filename, return_meta):
    with open(os.path.join(dirname, model_filename)) as f:
        meta = json.load(f)
    from ..core.desc import ProgramDesc

    program = Program()
    program.desc = ProgramDesc.from_dict(meta["program"])
    program.blocks = [framework.Block(program, i, desc=bd)
                      for i, bd in enumerate(program.desc.blocks)]
    for b in program.blocks:
        b.sync_with_desc()
    # a loaded program was not built by this process: verify its
    # structure before anything compiles it (cheap desc walk — no
    # infer-shape re-derivation; the serving engine's warmup runs the
    # full check).  Error findings raise ProgramVerificationError
    # naming op index + var.
    from .. import analysis

    analysis.verify_program(program, level="structural") \
        .publish(origin="io_load").raise_on_error()
    # load persistables recorded in the program
    vars = [v for v in program.list_vars() if v.persistable]
    load_vars(executor, dirname, vars=vars)
    fetch_vars = [program.global_block().var(n)
                  for n in meta["fetch_names"]]
    if return_meta:
        extra = {k: meta.get(k) for k in ("feed_meta", "bucket_hints")}
        return program, meta["feed_names"], fetch_vars, extra
    return program, meta["feed_names"], fetch_vars
