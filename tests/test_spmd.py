"""The SPMD mainline (paddle_tpu.spmd) on the virtual 8-device mesh.

Four contracts pinned here:

  * the plan artifact: regex rules layered over the `param_spec`
    heuristics, boundary behavior of `param_spec_reason` /
    `zero1_spec_reason` (exact min_shard_dim edges, precedence ties,
    non-divisible dims MUST carry a reason), save/load round-trip
    with a stable fingerprint, and the trainer refusing a plan built
    for a different mesh;
  * training parity: the plan-driven pjit step (fused GSPMD, the
    overlapped bucketed-ring schedule, and rules+zero1) produces the
    single-device losses and params on identical data;
  * resilience: sharded checkpoint save -> restore reassembles the
    exact state with NOTHING densified, and a supervisor attached via
    `attach_supervisor` auto-resumes a fresh trainer from the sharded
    snapshots.
"""

import json
import os

import numpy as np
import pytest

import jax
from jax.sharding import PartitionSpec as P

import paddle_tpu.fluid as fluid
from paddle_tpu.parallel import make_mesh
from paddle_tpu.parallel.mesh import parse_mesh_spec
from paddle_tpu.parallel.sharding import (param_spec_reason,
                                          zero1_spec_reason)
from paddle_tpu.spmd import (PartitionPlan, SpmdTrainer,
                             attach_supervisor, build_partition_plan,
                             load_rules, match_partition_rules)

BATCH, DIM, HIDDEN, CLASSES = 16, 8, 1024, 4


def _build_mlp():
    # same var names for every build so state dicts are comparable
    fluid.framework.reset_unique_name()
    main = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[BATCH, DIM],
                              dtype="float32", append_batch_size=False)
        label = fluid.layers.data(name="label", shape=[BATCH, 1],
                                  dtype="int64", append_batch_size=False)
        h = fluid.layers.fc(input=x, size=HIDDEN, act="relu")
        logits = fluid.layers.fc(input=h, size=CLASSES, act=None)
        loss = fluid.layers.softmax_with_cross_entropy(logits, label)
        avg = fluid.layers.mean(loss)
        fluid.optimizer.MomentumOptimizer(
            learning_rate=0.1, momentum=0.9).minimize(avg)
    return main, startup, avg


def _feeds(step):
    rs = np.random.RandomState(100 + step)
    return {
        "x": rs.rand(BATCH, DIM).astype(np.float32),
        "label": rs.randint(0, CLASSES,
                            size=(BATCH, 1)).astype(np.int64),
    }


def _run(mesh, steps=4, **kw):
    main, startup, avg = _build_mlp()
    tr = SpmdTrainer(main, startup, feed_names=["x", "label"],
                     fetch_names=[avg.name], mesh=mesh,
                     **kw).init()
    losses = []
    for i in range(steps):
        (loss,) = tr.step(_feeds(i))
        losses.append(float(np.asarray(loss).reshape(-1)[0]))
    params = {n: np.asarray(v) for n, v in tr.state.items()}
    return losses, params, tr


def _assert_parity(a, b):
    np.testing.assert_allclose(a[0], b[0], rtol=2e-5, atol=1e-6)
    assert a[1].keys() == b[1].keys()
    for n in a[1]:
        np.testing.assert_allclose(a[1][n], b[1][n],
                                   rtol=2e-4, atol=1e-5, err_msg=n)


# -- param_spec_reason / zero1_spec_reason boundaries ----------------------

class TestSpecReasons:
    mesh = parse_mesh_spec("dp=4,mp=2")  # static: zero devices needed

    def test_rows_vs_cols_precedence_tie(self):
        # rows == min_shard_dim*mp and rows == cols: the tie goes to
        # row sharding (the embedding-table rule fires first)
        spec, reason = param_spec_reason("w", (1024, 1024), self.mesh)
        assert spec == P("mp", None) and reason is None
        # one more col flips rows >= cols off; cols odd, rows still
        # divisible and >= min_shard_dim -> row sharding wins again
        spec, _ = param_spec_reason("w", (1024, 1025), self.mesh)
        assert spec == P("mp", None)
        # rows below the table threshold, cols divisible: cols win
        spec, reason = param_spec_reason("w", (512, 512), self.mesh)
        assert spec == P(None, "mp") and reason is None

    def test_min_shard_dim_exact_boundary(self):
        # 512 is IN (>= min_shard_dim), 511 is OUT — with odd cols the
        # row rule is the only path, so the boundary is visible alone
        spec, reason = param_spec_reason("w", (512, 511), self.mesh)
        assert spec == P("mp", None) and reason is None
        spec, reason = param_spec_reason("w", (511, 511), self.mesh)
        assert spec == P()
        assert "below min_shard_dim 512" in reason

    def test_non_divisible_dims_carry_a_reason(self):
        # both dims big enough but neither divides mp=2: forced
        # replication must explain itself (the S001 citation)
        spec, reason = param_spec_reason("w", (515, 515), self.mesh)
        assert spec == P()
        assert reason is not None and "not divisible" in reason
        # policy replication (non-2-D, or mp absent) has NO reason
        assert param_spec_reason("conv", (64, 3, 3, 3),
                                 self.mesh) == (P(), None)
        assert param_spec_reason("w", (515, 515),
                                 parse_mesh_spec("dp=8")) == (P(), None)

    def test_zero1_boundaries(self):
        mesh = parse_mesh_spec("dp=8")
        # exact boundary: dim == dp shards; scalar never does
        spec, reason = zero1_spec_reason(P(), (8,), mesh)
        assert spec == P("dp") and reason is None
        spec, reason = zero1_spec_reason(P(), (), mesh)
        assert spec == P() and "scalar" in reason
        # no free dim divides dp -> full copies, with the count cited
        spec, reason = zero1_spec_reason(P(), (7, 9), mesh)
        assert spec == P() and "8 full copies" in reason
        # a dim already taken by mp is skipped, not double-booked
        mesh2 = parse_mesh_spec("dp=4,mp=2")
        spec, reason = zero1_spec_reason(P("mp", None), (1024, 1024),
                                         mesh2)
        assert spec == P("mp", "dp") and reason is None
        # dp absent/1: base spec passes through untouched
        assert zero1_spec_reason(P(), (8,), parse_mesh_spec("mp=2")) \
            == (P(), None)


# -- the plan artifact -----------------------------------------------------

def test_rule_matching_precedence():
    rules = load_rules([[r"fc_.*\.w_0", ["mp", None]],
                        [r".*\.w_0", [None, "mp"]]])
    spec, pat = match_partition_rules(rules, "fc_1.w_0")
    assert spec == ("mp", None) and pat == r"fc_.*\.w_0"
    spec, _ = match_partition_rules(rules, "conv0.w_0")
    assert spec == (None, "mp")
    assert match_partition_rules(rules, "fc_1.b_0") == (None, None)


def test_plan_roundtrip_and_fingerprint(tmp_path):
    main, _startup, avg = _build_mlp()
    mesh = parse_mesh_spec("dp=4,mp=2")
    plan = build_partition_plan(main, mesh, ["x", "label"],
                                [avg.name])
    again = build_partition_plan(main, mesh, ["x", "label"],
                                 [avg.name])
    assert plan.fingerprint() == again.fingerprint()

    path = str(tmp_path / "plan.json")
    plan.save(path)
    loaded = PartitionPlan.load(path)
    assert loaded.fingerprint() == plan.fingerprint()
    assert loaded.var_specs == plan.var_specs
    assert loaded.mesh_axes == {"dp": 4, "mp": 2}
    # the summary is the human artifact: layout + comm floor render
    assert "comm" in plan.summary()


def test_rules_reshape_the_plan():
    main, _startup, avg = _build_mlp()
    mesh = parse_mesh_spec("dp=4,mp=2")
    base = build_partition_plan(main, mesh, ["x", "label"],
                                [avg.name])
    # the heuristic row-shards fc_1.w_0 (HIDDEN x CLASSES); the rule
    # flips it to column sharding — layout and fingerprint must move
    assert tuple(base.var_specs["fc_1.w_0"]) == ("mp", None)
    ruled = build_partition_plan(
        main, mesh, ["x", "label"], [avg.name],
        rules=load_rules([[r"fc_1\.w_0", [None, "mp"]]]))
    assert tuple(ruled.var_specs["fc_1.w_0"]) == (None, "mp")
    assert base.var_specs["fc_1.w_0"] != ruled.var_specs["fc_1.w_0"]
    assert base.fingerprint() != ruled.fingerprint()


def test_trainer_rejects_mismatched_plan():
    main, startup, avg = _build_mlp()
    plan = build_partition_plan(main, parse_mesh_spec("dp=2,mp=2"),
                                ["x", "label"], [avg.name])
    tr = SpmdTrainer(main, startup, feed_names=["x", "label"],
                     fetch_names=[avg.name],
                     mesh=make_mesh(n_devices=8), plan=plan)
    with pytest.raises(ValueError, match="pshard plan"):
        tr.init()


# -- training parity -------------------------------------------------------

def test_gspmd_step_matches_single_device():
    single = _run(make_mesh(n_devices=1))
    dp8 = _run(make_mesh(n_devices=8))
    assert all(np.isfinite(single[0]))
    assert dp8[2].step_mode == "gspmd"
    _assert_parity(dp8, single)


def test_overlapped_dp_matches_single_device():
    single = _run(make_mesh(n_devices=1))
    over = _run(make_mesh(n_devices=8), bucket_bytes=64 << 10)
    assert over[2].step_mode == "overlap-dp"
    _assert_parity(over, single)


def test_overlap_falls_back_with_reason():
    # zero1 breaks the replicated-params precondition: the trainer
    # must fall back to the fused path and say why
    _, _, tr = _run(make_mesh(n_devices=8), steps=1,
                    bucket_bytes=64 << 10, zero_stage=1)
    assert tr.step_mode == "gspmd"
    assert tr.overlap_fallback_reason


def test_rules_zero1_matches_single_device():
    single = _run(make_mesh(n_devices=1))
    sharded = _run(make_mesh(n_devices=8, mp=2), zero_stage=1,
                   rules=[[r"fc_1\.w_0", [None, "mp"]]])
    _assert_parity(sharded, single)
    # the rule really drove the compiled layout, not just the plan
    tr = sharded[2]
    assert tuple(tr.plan.var_specs["fc_1.w_0"]) == (None, "mp")
    assert "mp" in str(tr._shardings["fc_1.w_0"].spec)


# -- sharded checkpoints + supervisor resume -------------------------------

def test_sharded_checkpoint_roundtrip_no_densify(tmp_path):
    _, _, tr = _run(make_mesh(n_devices=8, mp=2), steps=2,
                    zero_stage=1)
    snap = tr.save_checkpoint(str(tmp_path), step=2)
    # the manifest-last discipline: the global manifest names the mesh
    manifest = json.load(
        open(os.path.join(snap, "_spmd_manifest.json")))
    assert manifest["mesh"] == {"dp": 4, "mp": 2}

    main, startup, avg = _build_mlp()
    fresh = SpmdTrainer(main, startup, feed_names=["x", "label"],
                        fetch_names=[avg.name],
                        mesh=make_mesh(n_devices=8, mp=2),
                        zero_stage=1).init()
    info = fresh.restore_checkpoint(str(tmp_path))
    assert info["step"] == 2 and info["densified"] == []
    for n in tr.state:
        np.testing.assert_array_equal(np.asarray(fresh.state[n]),
                                      np.asarray(tr.state[n]),
                                      err_msg=n)


def test_supervisor_auto_resume_sharded(tmp_path):
    root = str(tmp_path / "sup")
    _, _, tr = _run(make_mesh(n_devices=8, mp=2), steps=3,
                    zero_stage=1,
                    rules=[[r"fc_1\.w_0", ["mp", None]]])
    sup = attach_supervisor(tr, root, interval_secs=0.0)
    sup._saver.save(3)
    sup._saver.wait()

    # a relaunched job: fresh trainer, same programs, same mesh — the
    # supervisor must find the sharded snapshot and restore through
    # the saver protocol (never a dense scope checkpoint)
    main, startup, avg = _build_mlp()
    tr2 = SpmdTrainer(main, startup, feed_names=["x", "label"],
                      fetch_names=[avg.name],
                      mesh=make_mesh(n_devices=8, mp=2),
                      zero_stage=1,
                      rules=[[r"fc_1\.w_0", ["mp", None]]]).init()
    sup2 = attach_supervisor(tr2, root, interval_secs=0.0)
    assert sup2._latest_snapshot() is not None
    assert sup2._restore_latest() == 3
    for n in tr.state:
        np.testing.assert_array_equal(np.asarray(tr2.state[n]),
                                      np.asarray(tr.state[n]),
                                      err_msg=n)
