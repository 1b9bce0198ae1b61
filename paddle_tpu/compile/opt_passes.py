"""Cost-model-guided optimization passes: layout, fuse, auto_remat.

The PR 7 pipeline (compile/passes.py) only cleaned programs up
(dce/fold/cse/dve); these three passes are the "TVM direction" — each
is a Program->Program rewrite whose ACCEPT/DECLINE decision comes from
a cost model, not a heuristic flag:

  layout      NCHW->NHWC for conv/pool/bn chains via the
              fluid/data_transform.convert_layout machinery (minimal
              transpose insertion: one transform per var per layout
              boundary).  Accepted only when the TPU-tiled roofline
              (fluid/analysis.py ``tpu_tiling=True`` — minor dim pads
              to 128 lanes, second-minor to the dtype's sublanes)
              predicts a strictly lower max(MXU, HBM) ideal floor for
              the converted program.  Early nets with few channels
              (C < 128 pads catastrophically in NHWC) decline; deep
              conv stacks whose spatial dims shrank below the lane
              width accept.  Forward/inference programs only — a
              training program declines with a note (convert the
              forward BEFORE append_backward: fluid.convert_layout).

  fuse        greedy fusion of single-consumer elementwise/activation/
              bias chains into ``fused_elemwise_chain`` ops
              (fluid/fusion.py) — the chain's intermediates leave the
              IR, so the roofline's unique-bytes HBM floor drops and
              the verifier/segmenter walk fewer ops.  ``fuse:cap=N``
              bounds the fused-group size (0 = unbounded).  Declines
              without a fetch set, same contract as dce: fetch is a
              runtime by-name lookup the IR cannot see, and fusing
              away a fetched intermediate would break it.

  auto_remat  cost-model-driven activation checkpointing: when the
              liveness activation-peak estimate (the same accounting
              as the shard analyzer's S005) exceeds the per-device HBM
              budget, checkpoints are picked every ``stride`` forward
              ops (fluid/recompute.auto_checkpoints) and the backward
              region is rewritten to rematerialize forward segments
              (fluid/recompute.recompute_program).  Knobs:
              ``auto_remat:stride=N:budget_gb=G`` — G <= 0 forces the
              rewrite regardless of the estimate (the μ-cuDNN-style
              memory-vs-speed trade the tuner searches).

All three fold their knob settings into the PassManager's
``pipeline_id`` (compiled programs never alias across configs), keep
the verifier green around every rewrite, and preserve fetch numerics
bit-identically (f32) / within amp tolerance (bf16) — proven on the
golden fixtures by tests/test_opt_passes.py.
"""

from ..ops import registry as op_registry
from .passes import RewritePass, register_pass

__all__ = ["LayoutOptimize", "ElemwiseFusion", "AutoRemat",
           "DEFAULT_REMAT_BUDGET_GB", "activation_peak_bytes"]

# per-device HBM on the v5e class the benches run on; auto_remat's
# default budget (override per spec: auto_remat:budget_gb=...)
DEFAULT_REMAT_BUDGET_GB = 16.0


def _has_grad_ops(desc):
    return any(op_registry.is_grad_op_type(od.type)
               for b in desc.blocks for od in b.ops)


def _bf16_act_now():
    from ..utils import flags

    return bool(flags.get_flag("amp_bf16")
                and flags.get_flag("amp_bf16_act"))


def activation_peak_bytes(desc, fetches=()):
    """Peak live non-persistable bytes over block 0 — the activation
    term of the shard analyzer's S005 estimate, unsharded (dynamic
    dims count 1, so it is a floor).  The auto_remat accept gate.
    Shares the S005 walk (`dataflow.liveness_peak_bytes`); only the
    byte policy differs (amp activation element sizes here, shard
    specs there)."""
    from ..analysis.dataflow import liveness_peak_bytes
    from ..fluid import analysis as fluid_analysis

    bd = desc.block(0)
    bf16_act = _bf16_act_now()
    final_live = {n for n, vd in bd.vars.items() if vd.persistable}
    final_live |= set(fetches or ())

    def _act_bytes(n):
        vd = bd.vars.get(n)
        if vd is None or vd.persistable or vd.shape is None:
            return 0
        return fluid_analysis._numel(vd.shape) * \
            fluid_analysis._elem_bytes(str(vd.dtype), False, bf16_act)

    peak, _op = liveness_peak_bytes(bd.ops, _act_bytes, final_live)
    return peak


class LayoutOptimize(RewritePass):
    """NCHW->NHWC rewrite, accepted only on a predicted roofline win."""

    name = "layout"
    options = {"force": (int, 0)}  # 1 = skip the cost gate

    @staticmethod
    def _tiled_floor(program):
        from ..fluid import analysis

        rep = analysis.roofline_report(program, tpu_tiling=True,
                                       bf16_act=_bf16_act_now())
        return rep["floor_ms_ideal"]

    def run(self, desc, ctx):
        from ..fluid import data_transform, framework

        if not ctx.fetches:
            # same contract as dce/fuse: fetch is a runtime by-name
            # lookup the IR cannot see — without the fetch set the
            # layout guard below cannot protect an undeclared fetch of
            # an in-chain 4-D intermediate from observing permuted
            # values, so the pass declines
            ctx.note = "no fetch set; layout declines (dce contract)"
            return None
        if _has_grad_ops(desc):
            ctx.note = ("training program: layout must convert the "
                        "forward before append_backward "
                        "(fluid.convert_layout)")
            return None
        bd = desc.block(0)
        capable = [od for od in bd.ops
                   if od.type in data_transform.LAYOUT_CAPABLE]
        if not capable:
            ctx.note = "no layout-capable op (conv/pool/bn)"
            return None
        if any(od.attr("data_layout", "NCHW") == "NHWC"
               for od in capable):
            ctx.note = "program already runs NHWC"
            return None

        # trial conversion on a scratch clone prices the decision; the
        # base floor comes from a scratch parse too so both sides see
        # identical (desc-synced) metadata
        base = framework.Program.parse_from_string(
            desc.serialize_to_string())
        trial = framework.Program.parse_from_string(
            desc.serialize_to_string())
        trial_layout = {}
        data_transform.convert_layout(trial, to="NHWC",
                                      layout_out=trial_layout)
        # the rewrite keeps boundary values NCHW, but a fetch of an
        # in-chain 4-D intermediate would observe the permuted layout:
        # decline rather than change an observable value.  Membership
        # in the conversion's layout map is the test — shape
        # comparison misses C==H==W tensors, which permute to an
        # identical shape
        for name in sorted(ctx.fetches):
            if trial_layout.get(name) == "NHWC":
                ctx.note = "fetch %r changes layout; declined" % name
                return None
        floor_nchw = self._tiled_floor(base)
        floor_nhwc = self._tiled_floor(trial)
        if not self.force and floor_nhwc >= floor_nchw:
            ctx.note = ("tiled roofline predicts no win "
                        "(NCHW %.3f ms <= NHWC %.3f ms ideal floor)"
                        % (floor_nchw, floor_nhwc))
            return None
        n = data_transform.convert_layout(ctx.program, to="NHWC")
        diff = {"inserted_transposes": n,
                "converted_ops": len(capable),
                "floor_ms_ideal": {"nchw": round(floor_nchw, 6),
                                   "nhwc": round(floor_nhwc, 6)}}
        if self.force:
            diff["forced"] = True
        return diff


class ElemwiseFusion(RewritePass):
    """Greedy elementwise/activation/bias chain fusion (fluid/fusion)."""

    name = "fuse"
    options = {"cap": (int, 0)}  # max stages per fused op; 0 = unbounded

    def validate_options(self):
        if self.cap < 0 or self.cap == 1:
            raise ValueError("fuse:cap must be 0 (unbounded) or >= 2, "
                             "got %d" % self.cap)

    def run(self, desc, ctx):
        from ..fluid import fusion

        if not ctx.fetches:
            # same contract as dce: fetch is a runtime by-name lookup
            # the IR cannot see — fusing away a fetched intermediate
            # would break it, so without the fetch set nothing fuses
            ctx.note = "no fetch set; fusion declines (dce contract)"
            return None
        fused = fusion.fuse_elemwise_chains(
            desc, block_idx=0, keep=ctx.keep_names(0), cap=self.cap)
        if not fused:
            ctx.note = "no fusable single-consumer chain"
            return None
        return {"fused_chains": fused}


class AutoRemat(RewritePass):
    """Activation checkpointing when the peak estimate busts the HBM
    budget (fluid/recompute.py does the rewrite)."""

    name = "auto_remat"
    options = {"stride": (int, 8),
               "budget_gb": (float, DEFAULT_REMAT_BUDGET_GB)}

    def validate_options(self):
        if self.stride < 1:
            raise ValueError("auto_remat:stride must be >= 1, got %d"
                             % self.stride)

    def run(self, desc, ctx):
        from ..fluid import recompute
        from ..fluid.recompute import _RCP

        bd = desc.block(0)
        if not any(op_registry.is_grad_op_type(od.type)
                   for od in bd.ops):
            ctx.note = "no backward region to rematerialize into"
            return None
        if any(_RCP in n for n in bd.vars):
            ctx.note = "program already rematerialized"
            return None
        peak_before = activation_peak_bytes(desc, ctx.fetches)
        budget = self.budget_gb * (1 << 30)
        if self.budget_gb > 0 and peak_before <= budget:
            ctx.note = ("activation peak %.3f GiB within the %.1f GiB "
                        "budget" % (peak_before / 2**30, self.budget_gb))
            return None
        picks = recompute.auto_checkpoints(ctx.program,
                                           every=self.stride)
        if not picks:
            ctx.note = "no checkpointable forward op"
            return None
        cloned = recompute.recompute_program(ctx.program, picks)
        if not cloned:
            ctx.note = "nothing to rematerialize between checkpoints"
            return None
        peak_after = activation_peak_bytes(desc, ctx.fetches)
        return {"cloned_forward_ops": cloned,
                "checkpoints": len(picks),
                "stride": self.stride,
                "activation_peak_bytes": {"before": peak_before,
                                          "after": peak_after}}


register_pass(LayoutOptimize())
register_pass(ElemwiseFusion())
register_pass(AutoRemat())
