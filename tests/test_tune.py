"""paddle_tpu.tune: search space, static ranking (determinism +
S-code rejection), calibration fit, and the history hygiene the fit
depends on (docs/TUNING.md)."""

import json
import os
import subprocess
import sys

import pytest

from paddle_tpu.obs import perf as obs_perf
from paddle_tpu.tune import fit as tune_fit
from paddle_tpu.tune import models as tune_models
from paddle_tpu.tune import rank as tune_rank
from paddle_tpu.tune.rank import Calibration
from paddle_tpu.tune.space import (Candidate, SearchSpace,
                                   mesh_shapes_for)


# ---------------------------------------------------------------------------
# search space
# ---------------------------------------------------------------------------

def test_mesh_shapes_for_enumerates_factorizations():
    assert mesh_shapes_for(8) == [
        "dp=8,mp=1", "dp=4,mp=2", "dp=2,mp=4", "dp=1,mp=8"]
    assert mesh_shapes_for(1) == ["dp=1,mp=1"]
    # three axes: every ordered factorization, leading axis descending
    specs = mesh_shapes_for(4, axes=("dp", "mp", "sp"))
    assert specs[0] == "dp=4,mp=1,sp=1"
    assert "dp=2,mp=2,sp=1" in specs and "dp=1,mp=2,sp=2" in specs
    assert len(specs) == len(set(specs))


def test_space_constraints_never_enumerate_invalid_points():
    space = SearchSpace(8, batches=[12, 32], micro_batches=[1, 2],
                        pipelines=["none"])
    points = space.points()
    for cand in points:
        assert cand.batch % cand.dp == 0, cand
        assert (cand.batch // cand.dp) % cand.micro_batches == 0, cand
    # batch 12 cannot split over dp=8; per-device batch 12/dp=4 -> 3
    # cannot split over micro=2
    assert any("not divisible by dp" in r
               for r in space.skipped.values())
    assert any("micro_batches" in r for r in space.skipped.values())
    # deterministic enumeration: same space, same order
    again = SearchSpace(8, batches=[12, 32], micro_batches=[1, 2],
                        pipelines=["none"]).points()
    assert [c.tag() for c in points] == [c.tag() for c in again]


def test_space_rejects_invalid_knobs_at_construction():
    with pytest.raises(ValueError, match="axis product"):
        SearchSpace(8, meshes=["dp=4,mp=1"])
    with pytest.raises(ValueError, match="unknown pass"):
        SearchSpace(8, pipelines=["dce,not_a_pass"])
    with pytest.raises(ValueError):
        SearchSpace(8, meshes=["dq=8"])  # unknown axis name


def test_candidate_identity():
    cand = Candidate("dp=4,mp=2", "default", batch=64, micro_batches=2)
    assert cand.n_devices == 8 and cand.dp == 4
    assert cand.per_device_batch == 16
    assert cand.tag() == "dp4.mp2-b64-mb2-dce,fold,cse,dve"
    cfg = cand.config("lenet5")
    assert cfg["per_device_batch"] == 16
    assert cfg["pass_pipeline"] == "v1:dce,fold,cse,dve"
    # "none" and "" are the same pipeline, so one candidate — not two
    assert Candidate("dp=4,mp=2", "none", 64, 2) == \
        Candidate("dp=4,mp=2", "", 64, 2)


def test_space_pass_knob_dimensions():
    """fusion_caps/remat_strides cross only with pipelines carrying a
    bare fuse/auto_remat pass; invalid combos are skipped AT
    enumeration (never candidates), and the knob folds into the
    candidate's pipeline spec + pipeline id."""
    space = SearchSpace(
        4, meshes=["dp=4"], batches=[64], micro_batches=[1],
        pipelines=["none", "default+fuse+auto_remat"],
        fusion_caps=[0, 4], remat_strides=[0, 4])
    points = space.points()
    specs = [c.pipeline for c in points]
    assert "dce,fold,cse,dve,fuse:cap=4,auto_remat:stride=4" in specs
    assert "dce,fold,cse,dve,fuse,auto_remat" in specs
    # "none" never grows knobs; the knobbed combos with it are skipped
    assert "" in specs
    assert any("needs the fuse pass" in r for r in space.skipped.values())
    assert any("needs the auto_remat pass" in r
               for r in space.skipped.values())
    ids = {c.pipeline_id() for c in points}
    assert len(ids) == len(points)  # knob settings never alias
    # deterministic enumeration with knobs
    again = SearchSpace(
        4, meshes=["dp=4"], batches=[64], micro_batches=[1],
        pipelines=["none", "default+fuse+auto_remat"],
        fusion_caps=[0, 4], remat_strides=[0, 4]).points()
    assert [c.tag() for c in points] == [c.tag() for c in again]


def test_space_dedupes_default_valued_knob():
    """A knob spelled at its pass default ("auto_remat:stride=8" — 8
    IS the default) normalizes to the bare pass: the space must rank
    that pipeline ONCE, skipping the duplicate with a reason."""
    space = SearchSpace(
        4, meshes=["dp=4"], batches=[64], micro_batches=[1],
        pipelines=["default+auto_remat"], remat_strides=[0, 8])
    points = space.points()
    assert [c.pipeline for c in points] == \
        ["dce,fold,cse,dve,auto_remat"]
    assert any("duplicate point" in r for r in space.skipped.values())


def test_space_rejects_invalid_pass_knobs_at_construction():
    with pytest.raises(ValueError, match="fusion_caps"):
        SearchSpace(4, fusion_caps=[1])
    with pytest.raises(ValueError, match="remat_strides"):
        SearchSpace(4, remat_strides=[-1])
    # a knobbed pipeline spec with a bad knob value dies at
    # construction too (PassManager validates)
    with pytest.raises(ValueError, match="cap"):
        SearchSpace(4, pipelines=["default+fuse:cap=1"])


def test_space_skips_double_pinned_knob():
    space = SearchSpace(
        4, meshes=["dp=4"], batches=[64], micro_batches=[1],
        pipelines=["fuse:cap=2"], fusion_caps=[0, 4])
    points = space.points()
    assert [c.pipeline for c in points] == ["fuse:cap=2"]
    assert any("already pins" in r for r in space.skipped.values())


def test_space_knob_fold_preserves_repeated_other_passes():
    """Regression: the fold must rewrite the token LIST, not a
    name-keyed dict — a pipeline repeating some OTHER pass (dce twice)
    must keep both occurrences in the knobbed variant, or the knob A/B
    silently compares two different pipelines."""
    space = SearchSpace(
        4, meshes=["dp=4"], batches=[64], micro_batches=[1],
        pipelines=["dce,fuse,dce"], fusion_caps=[0, 4])
    specs = [c.pipeline for c in space.points()]
    assert specs == ["dce,fuse,dce", "dce,fuse:cap=4,dce"]


def test_space_skips_repeated_target_pass_knob_fold():
    """Folding a knob into a pipeline that repeats the TARGET pass is
    ambiguous: skipped with a reason, never a candidate."""
    space = SearchSpace(
        4, meshes=["dp=4"], batches=[64], micro_batches=[1],
        pipelines=["fuse,dce,fuse"], fusion_caps=[0, 4])
    specs = [c.pipeline for c in space.points()]
    assert specs == ["fuse,dce,fuse"]
    assert any("repeats the fuse pass" in r
               for r in space.skipped.values())


# ---------------------------------------------------------------------------
# static ranking
# ---------------------------------------------------------------------------

def _small_plan(hbm_gb=16, extra=(), meshes=("dp=8,mp=1", "dp=2,mp=4"),
                micro=(1, 2), calibration=None):
    space = SearchSpace(8, meshes=list(meshes), batches=[32],
                        micro_batches=list(micro), pipelines=["none"])
    return tune_rank.rank(
        tune_models.builder("lenet5"), space.points() + list(extra),
        8, model="lenet5", hbm_gb=hbm_gb, calibration=calibration,
        space_dict=space.to_dict(), skipped=space.skipped)


def test_rank_entries_carry_prices():
    plan = _small_plan()
    assert plan.ranked and not plan.rejected
    for e in plan.ranked:
        assert e.predicted_step_s > 0
        assert e.peak_hbm_bytes > 0
        assert set(e.terms) == {"compute_s", "comm_s", "overhead_s"}
        d = e.to_dict("lenet5")
        assert d["predicted_step_ms"] > 0
        assert "comm_wire_bytes" in d and "peak_hbm_bytes" in d
    # ascending predicted step time
    steps = [e.predicted_step_s for e in plan.ranked]
    assert steps == sorted(steps)


def test_rank_rejects_injected_s002_mesh():
    # 36 % dp=8 != 0: the analyzer's concrete-feed divisibility error
    bad = Candidate("dp=8,mp=1", "", batch=36, micro_batches=1)
    plan = _small_plan(extra=[bad])
    assert bad.tag() not in [e.candidate.tag() for e in plan.ranked]
    rej = {r.candidate.tag(): r for r in plan.rejected}
    assert rej[bad.tag()].code == "S002", rej


def test_rank_rejects_s005_over_hbm_citing_bytes():
    plan = _small_plan(hbm_gb=1e-6)
    assert not plan.ranked and plan.rejected
    for r in plan.rejected:
        assert r.code == "S005"
        assert r.peak_hbm_bytes and r.peak_hbm_bytes > 0
        # the message cites the per-device component bytes + budget
        assert "params" in r.message and "activation peak" in r.message
        assert "exceeds" in r.message and "budget" in r.message
        assert r.to_dict()["peak_hbm_bytes"] == r.peak_hbm_bytes


def test_rank_micro_batch_scales_activation_hbm():
    plan = _small_plan(meshes=("dp=8,mp=1",), micro=(1, 2))
    by_mb = {e.candidate.micro_batches: e for e in plan.ranked}
    assert by_mb[2].hbm_breakdown["activation_peak_bytes"] \
        < by_mb[1].hbm_breakdown["activation_peak_bytes"]
    assert by_mb[2].peak_hbm_bytes < by_mb[1].peak_hbm_bytes
    # ...at the price of overhead, not compute
    assert by_mb[2].terms["overhead_s"] > by_mb[1].terms["overhead_s"]
    assert by_mb[2].terms["compute_s"] == by_mb[1].terms["compute_s"]


def test_rank_prices_auto_remat_with_reduced_activation_peak():
    """An auto_remat candidate is analyzed over its PASS-OPTIMIZED
    program, so its S005 pricing uses the post-remat (reduced)
    liveness activation peak — and pays for it in the compute term
    (the recompute FLOPs/bytes are real)."""
    space = SearchSpace(
        8, meshes=["dp=8,mp=1"], batches=[32], micro_batches=[1],
        pipelines=["none", "default+auto_remat:stride=2:budget_gb=0"])
    plan = tune_rank.rank(
        tune_models.builder("lenet5"), space.points(), 8,
        model="lenet5", hbm_gb=16, space_dict=space.to_dict(),
        skipped=space.skipped)
    assert len(plan.ranked) == 2 and not plan.rejected
    by_pipe = {e.candidate.pipeline_label: e for e in plan.ranked}
    remat = by_pipe["dce,fold,cse,dve,auto_remat:budget_gb=0.0:stride=2"]
    raw = by_pipe["none"]
    assert remat.hbm_breakdown["activation_peak_bytes"] \
        < raw.hbm_breakdown["activation_peak_bytes"]
    assert remat.peak_hbm_bytes < raw.peak_hbm_bytes
    assert remat.terms["compute_s"] > raw.terms["compute_s"]


def test_rank_mesh_product_must_match_chips():
    off = Candidate("dp=2,mp=2", "", batch=32, micro_batches=1)
    plan = _small_plan(extra=[off])
    rej = {r.candidate.tag(): r for r in plan.rejected}
    assert rej[off.tag()].code == "MESH"


GOLDEN_ARGS = ["plan", "--model", "lenet5", "--chips", "8",
               "--hbm-gb", "16", "--batches", "32",
               "--micro-batches", "1,2", "--pipelines", "none,default",
               "--json"]


def test_rank_golden_snapshot_byte_identical_across_processes():
    """Determinism is the contract resumeFrom-style reproducibility
    rests on: two FRESH processes must emit byte-identical plans."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    outs = []
    for _ in range(2):
        proc = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.tools.tune_cli"]
            + GOLDEN_ARGS, cwd=repo, env=env, capture_output=True,
            text=True, timeout=300)
        assert proc.returncode == 0, proc.stderr[-2000:]
        outs.append(proc.stdout)
    assert outs[0] == outs[1]
    plan = json.loads(outs[0])
    assert plan["ranked"] and not plan["rejected"]
    # S001–S005-erroring meshes never appear ranked: every entry
    # re-parses into a candidate whose config is self-consistent
    for e in plan["ranked"]:
        assert e["config"]["batch"] % e["config"]["per_device_batch"] \
            == 0


# ---------------------------------------------------------------------------
# calibration + fit
# ---------------------------------------------------------------------------

def test_calibration_roundtrip_and_rank_application(tmp_path):
    cal = Calibration(coef={"compute": 2.0, "overhead": 3.0},
                      bias_s=0.001, n=4, model="lenet5",
                      error_before=0.5, error_after=0.05)
    path = str(tmp_path / "cal.json")
    cal.save(path)
    loaded = Calibration.load(path)
    assert loaded.to_dict() == cal.to_dict()
    assert not loaded.is_identity

    base = _small_plan(meshes=("dp=8,mp=1",), micro=(1,))
    calibrated = _small_plan(meshes=("dp=8,mp=1",), micro=(1,),
                             calibration=loaded)
    tag = base.ranked[0].candidate.tag()
    assert calibrated.entry(tag).predicted_step_s \
        != base.entry(tag).predicted_step_s
    assert calibrated.to_dict()["calibration"]["coef"]["compute"] == 2.0
    with pytest.raises(ValueError, match="unknown calibration term"):
        Calibration(coef={"wires": 2.0})


def _history_record(tag, step_ms, platform="cpu"):
    return {"leg": "ptune:" + tag, "step_ms": step_ms,
            "platform": platform, "metric": "m", "value": 1.0}


def test_fit_joins_history_and_error_decreases():
    plan = _small_plan(meshes=("dp=8,mp=1",), micro=(1, 2))
    # simulate measurements 50x slower than the floor predicts (a CPU
    # measuring a TPU-priced plan), plus rows fit must ignore: a
    # stale re-emit, a foreign leg, and an unknown tag
    records = []
    for e in plan.ranked:
        t = e.candidate.tag()
        meas = (e.terms["compute_s"] * 8 + e.terms["overhead_s"]) * 50
        records.append(_history_record(t, meas * 1e3))
    records.append(_history_record(plan.ranked[0].candidate.tag(),
                                   999.0, platform="tpu-stale"))
    records.append({"leg": "default-b128", "step_ms": 51.8,
                    "platform": "tpu"})
    records.append(_history_record("dp8.mp1-b99-mb1-none", 1.0))
    pairs = tune_fit.join_history(plan, records)
    assert len(pairs) == len(plan.ranked)
    cal = tune_fit.fit_calibration(pairs, model="lenet5")
    assert cal.n == len(pairs)
    assert cal.error_before > cal.error_after
    # the synthetic data is an exact linear model: the fit nails it
    assert cal.error_after < 0.01
    report = tune_fit.format_fit_report(cal, pairs)
    assert "median relative error" in report

    # the same join works from the serialized plan JSON (the artifact
    # `ptune fit --plan` loads)
    plan_dict = json.loads(plan.to_json())
    pairs2 = tune_fit.join_history(plan_dict, records)
    assert sorted(p["tag"] for p in pairs2) == \
        sorted(p["tag"] for p in pairs)


def test_fit_degenerate_inputs():
    plan = _small_plan(meshes=("dp=8,mp=1",), micro=(1,))
    # no measurements: the prior comes back unchanged
    ident = tune_fit.fit_calibration([], model="lenet5")
    assert ident.is_identity
    # one measurement: scalar fallback still reduces the error
    e = plan.ranked[0]
    meas = (e.terms["compute_s"] * 8 + e.terms["overhead_s"]) * 50
    pairs = tune_fit.join_history(
        plan, [_history_record(e.candidate.tag(), meas * 1e3)])
    cal = tune_fit.fit_calibration(pairs)
    assert cal.n == 1 and cal.error_after <= cal.error_before


# ---------------------------------------------------------------------------
# history hygiene (the prune-stale satellite + config blob)
# ---------------------------------------------------------------------------

def test_prune_stale_history_dry_run_then_apply(tmp_path):
    path = str(tmp_path / "hist.jsonl")
    rows = [
        {"metric": "a", "value": 1, "platform": "tpu", "step_ms": 5},
        {"metric": "b", "value": 2, "platform": "tpu-stale"},
        {"metric": "c", "value": 3, "platform": "cpu-fallback"},
        {"metric": "d", "value": 4, "platform": ""},
        {"metric": "e", "value": 5, "platform": "cpu"},
    ]
    with open(path, "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")
        f.write("torn line not json\n")
    # dry run reports but does not touch the file
    before = open(path).read()
    kept, dropped = obs_perf.prune_stale_history(path)
    assert kept == 3 and len(dropped) == 3  # a, e + the torn line
    assert {d["metric"] for d in dropped} == {"b", "c", "d"}
    assert open(path).read() == before
    # apply rewrites atomically, preserving the unparsable line
    kept, dropped = obs_perf.prune_stale_history(path, apply=True)
    lines = open(path).read().splitlines()
    assert len(lines) == 3 and "torn line not json" in lines
    metrics = [json.loads(l)["metric"] for l in lines
               if l.startswith("{")]
    assert metrics == ["a", "e"]
    # idempotent
    kept, dropped = obs_perf.prune_stale_history(path, apply=True)
    assert not dropped
    # missing file: no crash
    assert obs_perf.prune_stale_history(str(tmp_path / "nope")) \
        == (0, [])


def test_normalize_record_carries_config_blob():
    cfg = {"model": "lenet5", "mesh": "dp=8,mp=1", "batch": 4,
           "micro_batches": 2, "pass_pipeline": "v1:dce"}
    rec = {"metric": "m", "value": 1.0, "unit": "img/s",
           "step_ms": 9.0, "platform": "cpu", "config": cfg}
    norm = obs_perf.normalize_record(rec, leg="ptune:x")
    assert norm["config"] == cfg and norm["leg"] == "ptune:x"
    # records without one stay unchanged in shape
    rec.pop("config")
    assert "config" not in obs_perf.normalize_record(rec)


def test_ptune_cli_plan_in_process(tmp_path, capsys):
    from paddle_tpu.tools import tune_cli

    out = str(tmp_path / "plan.json")
    # --f32: the CLI's bf16 default flips process-global AMP state,
    # which must not leak into later tests
    rc = tune_cli.main(["plan", "--model", "lenet5", "--chips", "4",
                        "--meshes", "dp=4,mp=1", "--batches", "32",
                        "--micro-batches", "1", "--pipelines", "none",
                        "--hbm-gb", "16", "--f32", "--out", out])
    assert rc == 0
    text = capsys.readouterr().out
    assert "ranked launch plan" in text and "dp4.mp1-b32-mb1-none" \
        in text
    plan = json.load(open(out))
    assert plan["model"] == "lenet5" and len(plan["ranked"]) == 1


def test_ptune_plan_entries_round_trip_through_report(tmp_path, capsys):
    """A plan's entries are what the ranking priced and nothing that
    tells a program how to measure them; records tagged with an
    entry's leg join back to it through `ptune report`."""
    from paddle_tpu.tools import tune_cli

    out = str(tmp_path / "plan.json")
    assert tune_cli.main([
        "plan", "--model", "lenet5", "--chips", "4", "--meshes",
        "dp=4,mp=1", "--batches", "32", "--micro-batches", "1,2",
        "--pipelines", "none", "--hbm-gb", "16", "--f32", "--out",
        out]) == 0
    plan = json.load(open(out))
    assert len(plan["ranked"]) == 2
    for entry in plan["ranked"]:
        assert set(entry) == {
            "tag", "config", "predicted_step_ms",
            "predicted_samples_per_sec", "terms_ms", "comm_wire_bytes",
            "peak_hbm_bytes", "hbm_breakdown", "warnings"}
        assert entry["config"]["model"] == "lenet5"

    history = str(tmp_path / "history.jsonl")
    for entry in plan["ranked"]:
        obs_perf.append_history(
            {"metric": "m", "value": 1.0, "platform": "cpu",
             "step_ms": 50 * entry["predicted_step_ms"],
             "config": entry["config"]},
            history, leg=tune_fit.LEG_PREFIX + entry["tag"])
    capsys.readouterr()
    assert tune_cli.main(["report", "--plan", out, "--history", history,
                          "--json"]) == 0
    report = json.loads(capsys.readouterr().out)
    assert report["pairs"] == 2 and report["median_rel_error"] > 0.9
