"""paddle_tpu.compile.opt_passes — the cost-model-guided optimization
passes (layout / fuse / auto_remat) on the PassManager.

The load-bearing contracts:
  * every golden-fixture topology optimized through
    "default+layout+fuse+auto_remat" (and the forced-knob variant)
    keeps the verifier green and its fetches numerically equal —
    bit-identical in f32, tolerance-equal under amp_bf16;
  * pipeline ids are distinct per pass AND per knob setting;
  * the layout pass accepts/declines off the TPU-tiled roofline, and
    the layout/fuse-optimized ResNet-50 b256 program carries a
    strictly lower max(MXU, HBM) floor than the unoptimized one;
  * a deliberately-broken rewrite is rejected by the verifier before
    the desc can reach XLA.
"""

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.compile import opt_passes, passes
from paddle_tpu.core.ragged import RaggedTensor
from paddle_tpu.core.scope import Scope
from paddle_tpu.fluid import executor as executor_mod
from paddle_tpu.fluid.fusion import FUSED_ELEMWISE_OP
from paddle_tpu.utils import flags


@pytest.fixture(autouse=True)
def _reset_flags():
    yield
    flags.set_flag("compile_passes", "")
    fluid.amp.disable_bf16()


# ---------------------------------------------------------------------------
# golden-fixture builders (the canonical topologies the golden-IR tests
# pin) + concrete feeds so both the plain and the optimized program run
# ---------------------------------------------------------------------------

def _build_fit_a_line():
    x = fluid.layers.data(name="x", shape=[13], dtype="float32")
    y = fluid.layers.data(name="y", shape=[1], dtype="float32")
    pred = fluid.layers.fc(input=x, size=1)
    loss = fluid.layers.mean(
        x=fluid.layers.square_error_cost(input=pred, label=y))
    fluid.optimizer.SGD(learning_rate=0.01).minimize(loss)
    rs = np.random.RandomState(0)
    feed = {"x": rs.rand(4, 13).astype(np.float32),
            "y": rs.rand(4, 1).astype(np.float32)}
    return loss.name, feed


def _build_conv_classifier():
    img = fluid.layers.data(name="img", shape=[1, 28, 28],
                            dtype="float32")
    label = fluid.layers.data(name="label", shape=[1], dtype="int64")
    conv = fluid.layers.conv2d(input=img, num_filters=8, filter_size=3,
                               act="relu")
    pool = fluid.layers.pool2d(input=conv, pool_size=2, pool_stride=2)
    logits = fluid.layers.fc(input=pool, size=10, act="softmax")
    loss = fluid.layers.mean(
        x=fluid.layers.cross_entropy(input=logits, label=label))
    fluid.optimizer.MomentumOptimizer(learning_rate=0.01,
                                      momentum=0.9).minimize(loss)
    rs = np.random.RandomState(0)
    feed = {"img": rs.rand(4, 1, 28, 28).astype(np.float32),
            "label": rs.randint(0, 10, size=(4, 1)).astype(np.int64)}
    return loss.name, feed


def _build_dynamic_rnn():
    x = fluid.layers.data(name="x", shape=[8], dtype="float32",
                          lod_level=1)
    drnn = fluid.layers.DynamicRNN()
    with drnn.block():
        step = drnn.step_input(x)
        mem = drnn.memory(shape=[8], batch_ref=step, value=0.0)
        h = fluid.layers.fc(input=[step, mem], size=8, act="tanh")
        drnn.update_memory(mem, h)
        drnn.output(h)
    last = fluid.layers.sequence_last_step(input=drnn())
    loss = fluid.layers.mean(x=last)
    fluid.optimizer.SGD(learning_rate=0.01).minimize(loss)
    rs = np.random.RandomState(0)
    seqs = [rs.rand(n, 8).astype(np.float32) for n in (3, 5)]
    return loss.name, {"x": RaggedTensor.from_sequences(seqs)}


def _build_deepfm():
    from paddle_tpu.models.ctr import deepfm_ctr

    ids = fluid.layers.data(name="ids", shape=[4], dtype="int64")
    label = fluid.layers.data(name="label", shape=[1], dtype="float32")
    avg_loss, _ = deepfm_ctr(ids, label, num_features=64, num_fields=4,
                             embed_dim=4, hidden_sizes=(8,))
    fluid.optimizer.SGD(learning_rate=0.1).minimize(avg_loss)
    rs = np.random.RandomState(0)
    feed = {"ids": rs.randint(0, 64, size=(4, 4)).astype(np.int64),
            "label": rs.randint(0, 2, size=(4, 1)).astype(np.float32)}
    return avg_loss.name, feed


def _build_transformer():
    from paddle_tpu.models.transformer_program import (
        build_transformer_program, transformer_program_feeds)

    main, startup, avg_loss, _ = build_transformer_program(
        2, 8, 32, n_layer=1, n_head=2, d_model=16)
    with fluid.program_guard(main, startup):
        fluid.optimizer.MomentumOptimizer(
            learning_rate=0.01, momentum=0.9).minimize(avg_loss)
    from paddle_tpu.fluid import framework

    framework.switch_main_program(main)
    framework.switch_startup_program(startup)
    return avg_loss.name, transformer_program_feeds(2, 8, 32)


GOLDEN_BUILDERS = {
    "fit_a_line": _build_fit_a_line,
    "conv_classifier": _build_conv_classifier,
    "dynamic_rnn": _build_dynamic_rnn,
    "deepfm": _build_deepfm,
    "transformer": _build_transformer,
}

# the acceptance pipeline, plus a variant that FORCES every opt pass to
# fire (layout ignores the cost gate, auto_remat's budget is 0) with
# non-default knobs so the knob plumbing is numerically covered too
PIPELINES = [
    "default+layout+fuse+auto_remat",
    "default+layout:force=1+fuse:cap=2+auto_remat:stride=2:budget_gb=0",
]


def _snap_scope(scope):
    """Deep-copy snapshot: the executor donates param buffers on the
    in-place update path, so shared arrays would be deleted by the
    first run."""
    import jax

    s = Scope()
    for n in scope.local_var_names():
        v = scope.get(n)
        if isinstance(v, jax.Array):
            v = jax.device_put(np.asarray(v))
        s.set_local(n, v)
    return s


def _run_both(main, opt, fetch, feed):
    """Run plain and optimized from IDENTICAL initial params (one
    startup run, snapshotted per program)."""
    startup = fluid.default_startup_program()
    exe = executor_mod.Executor(executor_mod.CPUPlace())
    base = Scope()
    with executor_mod.scope_guard(base):
        exe.run(startup)
    outs = []
    for prog in (main, opt):
        with executor_mod.scope_guard(_snap_scope(base)):
            outs.append(np.asarray(
                exe.run(prog, feed=feed, fetch_list=[fetch])[0]))
    return outs


class TestGoldenFixtureNumerics:
    @pytest.mark.parametrize("pipeline", PIPELINES)
    @pytest.mark.parametrize("case", sorted(GOLDEN_BUILDERS))
    def test_fetches_bit_identical_f32(self, case, pipeline):
        fetch, feed = GOLDEN_BUILDERS[case]()
        main = fluid.default_main_program()
        pm = passes.PassManager(pipeline, verify_level="full")
        opt = pm.run(main, fetches=[fetch])
        plain, optimized = _run_both(main, opt, fetch, feed)
        np.testing.assert_array_equal(plain, optimized)

    def test_amp_bf16_tolerance_equal(self):
        fluid.amp.enable_bf16()
        fetch, feed = _build_conv_classifier()
        main = fluid.default_main_program()
        pm = passes.PassManager(PIPELINES[1], verify_level="structural")
        opt = pm.run(main, fetches=[fetch])
        plain, optimized = _run_both(main, opt, fetch, feed)
        np.testing.assert_allclose(plain, optimized, rtol=5e-2,
                                   atol=5e-2)

    def test_forced_pipeline_actually_rewrites(self):
        # budget_gb=0 forces remat on the training fixture — the
        # acceptance spec must not green-light a no-op pipeline
        fetch, _ = _build_conv_classifier()
        pm = passes.PassManager(PIPELINES[1])
        pm.run(fluid.default_main_program(), fetches=[fetch])
        changed = {r["pass"]: r["changed"] for r in pm.records}
        assert changed["auto_remat:budget_gb=0.0:stride=2"], pm.records


class TestSpecGrammar:
    def test_plus_separator_equals_comma(self):
        a = passes.PassManager("default+layout+fuse")
        b = passes.PassManager("dce,fold,cse,dve,layout,fuse")
        assert a.pipeline_id == b.pipeline_id

    def test_pipeline_ids_distinct_per_knob(self):
        specs = ["default",
                 "default+layout+fuse",
                 "default+layout+fuse:cap=2",
                 "default+layout+fuse:cap=4",
                 "default+layout+fuse+auto_remat",
                 "default+layout+fuse+auto_remat:stride=2",
                 "default+layout+fuse+auto_remat:stride=4",
                 "default+layout+fuse+auto_remat:stride=4:budget_gb=0"]
        ids = [passes.pipeline_id(s) for s in specs]
        assert len(set(ids)) == len(ids), ids

    def test_explicit_default_knob_is_same_pipeline(self):
        # "fuse:cap=0" IS the bare fuse pass: one semantics -> one
        # pipeline id
        assert passes.pipeline_id("fuse:cap=0") == \
            passes.pipeline_id("fuse")
        assert passes.pipeline_id("layout:force=0") == \
            passes.pipeline_id("layout")
        assert passes.pipeline_id("fuse:cap=4") != \
            passes.pipeline_id("fuse")

    def test_float_knob_token_reparses(self):
        # '%g' rendered 2e6 as '2e+06', whose '+' is a token
        # separator — the canonical spec must round-trip through the
        # parser
        pid = passes.pipeline_id("auto_remat:budget_gb=2000000")
        spec = passes.PassManager(
            "auto_remat:budget_gb=2000000", verify=False).spec
        assert passes.pipeline_id(spec) == pid
        assert "+" not in spec

    def test_unknown_knob_rejected(self):
        with pytest.raises(ValueError, match="no option"):
            passes.PassManager("fuse:nope=1")

    def test_invalid_knob_value_rejected(self):
        with pytest.raises(ValueError, match="cap"):
            passes.PassManager("fuse:cap=1")
        with pytest.raises(ValueError, match="stride"):
            passes.PassManager("auto_remat:stride=0")

    def test_malformed_token_rejected(self):
        with pytest.raises(ValueError, match="malformed"):
            passes.PassManager("fuse:cap")


class TestLayoutPass:
    def _forward_conv(self, channels):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            img = fluid.layers.data(
                name="img", shape=[4, channels, 8, 8], dtype="float32",
                append_batch_size=False)
            conv = fluid.layers.conv2d(input=img, num_filters=channels,
                                       filter_size=3, padding=1,
                                       act="relu")
            pool = fluid.layers.pool2d(input=conv, pool_size=8,
                                       pool_type="avg",
                                       global_pooling=True)
            out = fluid.layers.fc(input=pool, size=4)
        return main, out.name

    def test_declines_without_fetches(self):
        # the fetch-layout guard cannot protect an undeclared runtime
        # fetch: without a fetch set the pass declines, like dce/fuse
        main, fetch = self._forward_conv(8)
        pm = passes.PassManager("layout:force=1")
        opt = pm.run(main, fetches=[])
        assert not pm.records[0]["changed"]
        assert "dce contract" in pm.records[0]["note"]
        assert opt.desc.serialize_to_string() == \
            main.desc.serialize_to_string()

    def test_declines_on_training_program(self):
        fetch, _feed = _build_conv_classifier()
        pm = passes.PassManager("layout:force=1")
        opt = pm.run(fluid.default_main_program(), fetches=[fetch])
        rec = pm.records[0]
        assert not rec["changed"] and "before append_backward" \
            in rec["note"]
        assert opt.desc.serialize_to_string() == \
            fluid.default_main_program().desc.serialize_to_string()

    def test_cost_gate_declines_tiny_channels(self):
        # C=8 pads to 128 lanes in NHWC: the tiled roofline says NCHW
        # is cheaper and the pass must decline on its own
        main, fetch = self._forward_conv(8)
        pm = passes.PassManager("layout")
        opt = pm.run(main, fetches=[fetch])
        rec = pm.records[0]
        assert not rec["changed"] and "no win" in rec["note"]
        assert all(od.attr("data_layout", "NCHW") == "NCHW"
                   for od in opt.global_block().desc.ops)

    def test_fetched_intermediate_declines_even_shape_invariant(self):
        """Regression: a fetched in-chain 4-D var with C==H==W
        permutes NCHW->NHWC to an IDENTICAL shape — the fetch guard
        must test layout-map membership, not shape equality, or the
        fetch silently returns permuted data."""
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            img = fluid.layers.data(name="img", shape=[2, 8, 8, 8],
                                    dtype="float32",
                                    append_batch_size=False)
            conv = fluid.layers.conv2d(input=img, num_filters=8,
                                       filter_size=3, padding=1,
                                       act="relu")
            out = fluid.layers.reduce_sum(conv)
        # conv output shape [2, 8, 8, 8]: permutation-invariant
        mid = next(od.output("Out")[0]
                   for od in main.global_block().desc.ops
                   if od.type == "relu")
        pm = passes.PassManager("layout:force=1", explain=True)
        opt = pm.run(main, fetches=[mid, out.name])
        rec = pm.records[0]
        assert not rec["changed"], rec
        assert "changes layout" in rec["note"]
        assert opt.desc.serialize_to_string() == \
            main.desc.serialize_to_string()

    def test_force_converts_and_preserves_numerics(self):
        main, fetch = self._forward_conv(8)
        startup = fluid.Program()  # params live in main's startup
        pm = passes.PassManager("layout:force=1", verify_level="full",
                                explain=True)
        opt = pm.run(main, fetches=[fetch])
        rec = pm.records[0]
        assert rec["changed"] and rec["diff"]["inserted_transposes"] >= 1
        assert any(od.attr("data_layout") == "NHWC"
                   for od in opt.global_block().desc.ops)


class TestFusePass:
    def _residual_forward(self):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data(name="x", shape=[4, 8],
                                  dtype="float32",
                                  append_batch_size=False)
            y = fluid.layers.scale(x=x, scale=2.0)
            z = fluid.layers.elementwise_add(x=x, y=y)
            r = fluid.layers.relu(z)
            out = fluid.layers.reduce_sum(r)
        return main, out.name

    def test_fuses_chain_and_numerics(self):
        main, fetch = self._residual_forward()
        pm = passes.PassManager("fuse", verify_level="full")
        opt = pm.run(main, fetches=[fetch])
        types = [od.type for od in opt.global_block().desc.ops]
        assert FUSED_ELEMWISE_OP == "fused_elemwise_chain"
        assert "fused_elemwise_chain" in types
        # scale -> add -> relu collapse into one op
        assert "relu" not in types and "elementwise_add" not in types
        xv = np.random.RandomState(0).rand(4, 8).astype(np.float32)
        exe = executor_mod.Executor(executor_mod.CPUPlace())
        with executor_mod.scope_guard(Scope()):
            a = np.asarray(exe.run(main, feed={"x": xv},
                                   fetch_list=[fetch])[0])
            b = np.asarray(exe.run(opt, feed={"x": xv},
                                   fetch_list=[fetch])[0])
        np.testing.assert_array_equal(a, b)

    def test_cap_bounds_group_size(self):
        main, fetch = self._residual_forward()
        pm = passes.PassManager("fuse:cap=2")
        opt = pm.run(main, fetches=[fetch])
        for od in opt.global_block().desc.ops:
            if od.type == FUSED_ELEMWISE_OP:
                assert len(od.attr("inner_types")) <= 2

    def test_fetched_intermediate_never_fused(self):
        main, _ = self._residual_forward()
        # fetch the chain intermediate: the chain must stop before it
        mid = next(od.output("Out")[0]
                   for od in main.global_block().desc.ops
                   if od.type == "elementwise_add")
        pm = passes.PassManager("fuse")
        opt = pm.run(main, fetches=[mid])
        assert mid in opt.global_block().desc.vars
        types = [od.type for od in opt.global_block().desc.ops]
        assert "relu" in types  # consumer of the fetched value survives

    def test_declines_without_fetches(self):
        main, _ = self._residual_forward()
        pm = passes.PassManager("fuse")
        opt = pm.run(main, fetches=[])
        assert not pm.records[0]["changed"]
        assert "dce contract" in pm.records[0]["note"]

    def test_multi_use_intermediate_not_fused(self):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data(name="x", shape=[4, 8],
                                  dtype="float32",
                                  append_batch_size=False)
            y = fluid.layers.relu(x)
            a = fluid.layers.scale(x=y, scale=2.0)
            b = fluid.layers.scale(x=y, scale=3.0)  # second use of y
            out = fluid.layers.elementwise_add(x=a, y=b)
        pm = passes.PassManager("fuse")
        opt = pm.run(main, fetches=[out.name])
        types = [od.type for od in opt.global_block().desc.ops]
        assert "relu" in types  # y has two consumers: never fused away


class TestAutoRematPass:
    def test_declines_within_budget(self):
        fetch, _feed = _build_conv_classifier()
        pm = passes.PassManager("auto_remat")  # 16 GiB default budget
        pm.run(fluid.default_main_program(), fetches=[fetch])
        rec = pm.records[0]
        assert not rec["changed"] and "within" in rec["note"]

    def test_forced_remat_reduces_activation_peak(self):
        fetch, _feed = _build_conv_classifier()
        main = fluid.default_main_program()
        before = opt_passes.activation_peak_bytes(main.desc, [fetch])
        pm = passes.PassManager("auto_remat:stride=2:budget_gb=0",
                                explain=True)
        opt = pm.run(main, fetches=[fetch])
        rec = pm.records[0]
        assert rec["changed"]
        peaks = rec["diff"]["activation_peak_bytes"]
        assert peaks["before"] == before
        assert peaks["after"] < peaks["before"]
        assert any("recompute_barrier" == od.type
                   for od in opt.global_block().desc.ops)

    def test_declines_on_forward_program(self):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data(name="x", shape=[4], dtype="float32")
            out = fluid.layers.scale(x=x, scale=2.0)
        pm = passes.PassManager("auto_remat:budget_gb=0")
        pm.run(main, fetches=[out.name])
        assert not pm.records[0]["changed"]
        assert "backward" in pm.records[0]["note"]


class TestVerifierRejection:
    def test_broken_opt_rewrite_rejected(self, monkeypatch):
        from paddle_tpu.analysis.diagnostics import \
            ProgramVerificationError

        class BreakIR(passes.RewritePass):
            name = "fuse"  # masquerade in the registry slot

            def run(self, desc, ctx):
                # drop a var another op still reads: V002
                bd = desc.block(0)
                victim = next(n for n, vd in bd.vars.items()
                              if not vd.persistable)
                del bd.vars[victim]
                return {"broke": [victim]}

        monkeypatch.setitem(passes._PASSES, "fuse", BreakIR())
        fetch, _feed = _build_conv_classifier()
        with pytest.raises(ProgramVerificationError):
            passes.PassManager("fuse").run(
                fluid.default_main_program(), fetches=[fetch])


class TestResnet50B256Floor:
    def test_layout_fuse_strictly_lower_max_floor(self):
        """ISSUE 14 acceptance: the roofline cost model must predict a
        strictly lower max(MXU, HBM) floor for the layout/fuse-
        optimized ResNet-50 b256 program than for the unoptimized
        one — under the tiled accounting the layout gate uses AND
        under the default accounting (the fuse win alone)."""
        from paddle_tpu import models
        from paddle_tpu.fluid import analysis

        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            image = fluid.layers.data(
                name="image", shape=[256, 3, 224, 224],
                dtype="float32", append_batch_size=False)
            logits = models.resnet50(image, class_dim=1000)
        pm = passes.PassManager("default+layout+fuse")
        opt = pm.run(main, fetches=[logits.name])
        changed = {r["pass"]: r["changed"] for r in pm.records}
        # layout must be accepted by its OWN cost gate (not forced),
        # and fuse must find the residual add+relu chains
        assert changed["layout"] and changed["fuse"], pm.records

        def max_floor(prog, tiled):
            rep = analysis.roofline_report(prog, tpu_tiling=tiled)
            return max(rep["total_gflops"] * 1e9
                       / (rep["peak_tflops"] * 1e12),
                       rep["unique_gbytes"] / rep["hbm_gbps"])

        assert max_floor(opt, True) < max_floor(main, True)
        assert max_floor(opt, False) < max_floor(main, False)


class TestTiledRoofline:
    def test_tile_padding_math(self):
        from paddle_tpu.fluid.analysis import _numel_tiled

        assert _numel_tiled((4, 7, 7), 4) == 4 * 8 * 128
        assert _numel_tiled((4, 7, 7), 2) == 4 * 16 * 128
        assert _numel_tiled((256,), 4) == 256 * 8
        assert _numel_tiled((300,), 4) == 384 * 8
        assert _numel_tiled((), 4) == 8 * 128
        assert _numel_tiled((2, 8, 128), 4) == 2 * 8 * 128

    def test_report_flags_tiling(self):
        _fetch, _feed = _build_fit_a_line()
        from paddle_tpu.fluid import analysis

        main = fluid.default_main_program()
        plain = analysis.roofline_report(main)
        tiled = analysis.roofline_report(main, tpu_tiling=True)
        assert not plain["tpu_tiling"] and tiled["tpu_tiling"]
        assert tiled["unique_gbytes"] >= plain["unique_gbytes"]
