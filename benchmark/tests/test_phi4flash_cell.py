"""The decoder-hybrid-decoder cell `phi4flash-turn-16k`: its files found
by name, its driver end to end as a CPU rehearsal at a toy size (fixture
`phi4flash-tiny-turn`, found through `--search-path`), the six controls
that `correct` has to refuse, the weights' draw, the parameter and byte
arithmetic of flops/yoco.py against hand counts and ISSUE 63's numbers,
the new readers on a written trace and on a recording from the chip,
every reader the benchmark already had on this cell's facts with a
chip's peaks set, and BENCHMARK.json's entries for the cell.
"""

import json
import os
import types

import numpy as np
import pytest

from benchmark.harness import CHECKOUT, Lookup
from benchmark.reduce import op_scopes, share_ops, xplane, yoco_ops
from benchmark.tests import yoco_control
from benchmark.tests.test_run import FIXTURE, last_line, run_cell

CELL = "phi4flash-turn-16k"
CONFIG = "phi-4-mini-flash-reasoning"
TOY, TOY_CONFIG = "phi4flash-tiny-turn", "phi4flash-tiny"
NEW_READERS = ("shared_kv_attn_ms_per_step", "shared_kv_attn_roofline",
               "diff_window_ms_per_step", "ssm_step_ms_per_step",
               "ssm_step_roofline", "gmu_ms_per_step",
               "cross_positions_share", "yoco_decode_hbm_roofline")
SHARED_READERS = ("decoder_prep_ms_per_call", "decoder_idle_ms_per_call",
                  "prefill_device_ms_per_call", "decode_device_step_ms",
                  "decode_unscoped_ms_per_step", "decode_trace_lower_s")
LIMITED = ("gap_mean", "not_first_share", "attn_off_window",
           "attn_off_full", "attn_off_cross", "attn_off_first", "ssm_off",
           "ssm_off_first", "gmu_off")
# the control, and a limit of its own that refuses it at the toy size
# (`own_slot_share` is no limit: `compare` reports it, and it is what
# tells a stale cross read from a sound one)
CONTROLS = {"serve_dtype=float8_e4m3fn": "attn_off_full",
            "state_dtype=bfloat16": "ssm_off_first",
            "window=4": "attn_off_window",
            "control.subtract=false": "attn_off_cross",
            "control.memory_after_gate=true": "gmu_off",
            "control.cross_before_write=true": "own_slot_share"}
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
LOOKUP = Lookup([FIXTURE])
yoco = LOOKUP.module("flops", "yoco")


# -- the cell's files, by name --------------------------------------------------

def test_the_cells_files_are_found_by_name():
    workload = LOOKUP.json("workloads", CELL)
    config = LOOKUP.json("configs", workload["config"])
    assert config["name"] == CONFIG
    for kind, name in (("drivers", workload["driver"]),
                       ("models", workload["builder"]),
                       ("reference", workload["reference"]),
                       ("flops", "yoco"), ("reduce", "yoco_ops")):
        assert os.path.dirname(LOOKUP.path(kind, name + ".py")).endswith(kind)
    assert set(NEW_READERS) <= set(LOOKUP.names("layer_metrics"))
    assert (config["builder"], config["reference"]) == \
        (workload["builder"], workload["reference"])


def test_the_reference_is_in_the_repository_twice():
    with open(LOOKUP.path("reference", "phi4_flash.py")) as f:
        copy = f.read()
    with open(os.path.join(CHECKOUT, "paddle_tpu", "models", "reference",
                           "phi4_flash.py")) as f:
        assert f.read() == copy
    assert "paddle_tpu" not in copy.split('"""', 2)[2]


# -- the driver, end to end -----------------------------------------------------

def test_untraced_rehearsal_has_exactly_the_two_metrics():
    result = last_line(run_cell(TOY, 0))
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"decode_tok_per_s", "setup_s"}
    assert result["attempted"] % 4 == 0 and result["attempted"] >= 8
    assert result["device"]["platform"] == "cpu"


def test_traced_rehearsal_reads_counters_and_no_device_metric():
    proc = run_cell(TOY, 1)
    result = last_line(proc)
    metrics = result["metrics"]
    assert result["correct"] is True
    assert metrics["compiles_in_window"] == {"value": 0, "unit": "count"}
    assert {"setup_compile_s", "setup_cache_misses",
            "decode_trace_lower_s"} <= set(metrics)
    # what only a chip can say: this cell's and the other generation cells'
    assert not (set(NEW_READERS) | {
        "long_decode_step_ms", "kv_attn_ms_per_step", "decode_step_ms",
        "decode_hbm_roofline", "gdn_ms_per_step"}) & set(metrics)
    for stream in (proc.stdout, proc.stderr):
        for name in LIMITED:
            assert "check ok  : %s" % name in stream
        assert "check ok  : no compile inside the windows (0), limit 0" \
            in stream
    assert "session as handed in: cache" in proc.stdout
    assert "by layer (mwmwmfgc)" in proc.stdout


@pytest.mark.parametrize("change, said", [
    (dict(gen_len=26), "do not fit 64 cache positions"),
    (dict(session_len=24), "not whole turns"),
])
def test_a_session_that_does_not_fit_is_refused_before_the_first_call(
        tmp_path, change, said):
    import subprocess
    import sys

    workload = dict(LOOKUP.json("workloads", TOY), **change)
    os.makedirs(tmp_path / "workloads")
    with open(tmp_path / "workloads" / "too-long.json", "w") as f:
        json.dump(workload, f)
    proc = subprocess.run(
        [sys.executable, os.path.join(CHECKOUT, "benchmark", "run.py"),
         "--workload", "too-long", "--seed", "5", "--seconds", "1",
         "--search-path", str(tmp_path), "--search-path", FIXTURE],
        cwd=CHECKOUT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600)
    assert proc.returncode != 0
    assert said in proc.stderr


# -- what `correct` has to refuse -----------------------------------------------

@pytest.fixture(scope="module")
def readings():
    """{control: decode_yoco.compare's numbers} at the toy size, seed 5:
    the sound call and every control from one reference session."""
    import jax

    workload = dict(LOOKUP.json("workloads", TOY), name=TOY)
    return workload["correct"], dict(yoco_control.readings(
        LOOKUP, workload, 5, jax.devices()[:1], None,
        [None] + list(CONTROLS)))


def test_the_sound_path_keeps_the_limits(readings):
    limits, found = readings
    sound = found[None]
    assert set(limits) - {"why"} == set(LIMITED)
    assert all(sound[n] <= limits[n] for n in LIMITED), sound
    assert sound["own_slot_share"] == pytest.approx(1.0, abs=1e-3)
    assert sound["rows"] == 2 and sound["tokens"] == 2 * 24
    assert len(sound["off_by_layer"]) == 8


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_the_control_is_not_correct(readings, control):
    """Each control is refused by a limit of its own; the float8 caches,
    the narrow window and the dropped subtraction also by the served
    tokens (`gap_mean`).  (A state carried in bfloat16 and a cross layer
    that misses its own slot move no token of 48 at the toy size: their
    own limits are what refuses them here.)"""
    limits, found = readings
    got = found[control]
    own = CONTROLS[control]
    if own == "own_slot_share":
        # not quite 0: the slot a stale read finds holds zeros, a key
        # of score 0 and no value, where the reference's holds nothing
        assert abs(got[own]) < 0.1 and own not in limits
        assert got["attn_off_cross"] > limits["attn_off_cross"]
    else:
        assert got[own] > limits[own], got
    if control in ("serve_dtype=float8_e4m3fn", "window=4",
                   "control.subtract=false",
                   "control.memory_after_gate=true"):
        assert got["gap_mean"] > limits["gap_mean"], got


def test_a_mamba_layers_probes_hold_what_its_scan_read():
    """`compare` judges what is one position's (a scan's output, a
    memory unit's) on the program's own input to it: a Mamba layer's
    probes carry the convolved input its scan read beside its output."""
    cfg, workload, model = _toy()
    built = model.build(cfg, workload["batch"])
    block = built["main"].global_block()
    d_inner = 2 * cfg["hidden_size"]
    for (i, pairs), kind in zip(built["probes"], model.kinds(cfg)):
        assert set(pairs) == {"in", "out"} | ({"xc"} if kind == "mamba"
                                              else set())
        if kind == "mamba":
            assert [block.var(pairs[what][1]).shape for what in
                    ("xc", "out")] == [(workload["batch"], 1, d_inner)] * 2


# -- the weights ---------------------------------------------------------------------

def _toy():
    cfg = LOOKUP.json("configs", TOY_CONFIG)
    workload = LOOKUP.json("workloads", TOY)
    return cfg, workload, LOOKUP.module("models", workload["builder"])


def test_the_weights_draw():
    """A block made alone is the block of the whole tree, bit for bit;
    the scan's own parameters are Mamba's start (A = 1 .. N along the
    state, D = 1, steps log-uniform in [0.001, 0.1]); biases and
    lambda's vectors are float32; the queries' and the scan's low-rank
    projections carry their gains."""
    import jax

    cfg, workload, model = _toy()
    spec = dict(workload["weights"], dtype="bfloat16")
    key = jax.random.PRNGKey(3)
    tree = jax.jit(lambda k: model.weights(cfg, spec, k))(key)
    alone = jax.jit(lambda k: model.block(cfg, spec, model.root(k), 4))(key)
    for name, value in alone.items():
        assert np.array_equal(np.asarray(value, np.float32),
                              np.asarray(tree["blocks"][4][name],
                                         np.float32)), name
    block = tree["blocks"][0]
    assert np.allclose(np.exp(np.asarray(block["a_log"])),
                       np.arange(1, 5)[None, :])
    assert np.all(np.asarray(block["d"]) == 1.0)
    dt = np.log1p(np.exp(np.asarray(block["dt_bias"], np.float64)))
    assert dt.min() >= 1e-3 * 0.999 and dt.max() <= 1e-1 * 1.001
    assert dt.max() / dt.min() > 10
    for name in ("ln1.b", "conv_b", "dt_bias", "a_log", "d"):
        assert block[name].dtype == np.float32, name
    assert tree["blocks"][1]["lq1"].dtype == np.float32
    assert block["in_proj"].dtype == block["ln1.w"].dtype == "bfloat16"
    std = lambda a: float(np.std(np.asarray(a, np.float32)))
    assert std(tree["blocks"][1]["wq"]) == pytest.approx(
        spec["std"] * spec["q_gain"], rel=0.1)
    assert std(block["x_proj"]) == pytest.approx(
        spec["std"] * spec["ssm_gain"], rel=0.1)
    assert std(block["conv_w"]) == pytest.approx(spec["conv_std"], rel=0.1)
    assert "wkv" not in tree["blocks"][7] and "wkv" in tree["blocks"][5]
    assert set(tree["blocks"][6]) == {"ln1.w", "ln1.b", "ln2.w", "ln2.b",
                                      "ffn_in", "ffn_out", "gmu_in",
                                      "gmu_out"}
    assert set(tree) == {"embed", "blocks", "norm_f"}


def test_the_model_is_the_files_draw_and_the_traffic_is_the_seeds():
    workload = LOOKUP.json("workloads", CELL)
    assert workload["weights"]["seed"] == 6300000501
    cfg, toy, model = _toy()
    one, two = (model.documents(cfg, toy, seed) for seed in (5, 6))
    assert one.shape == (2, 32) and not np.array_equal(one, two)
    assert np.array_equal(one, model.documents(cfg, toy, 5))
    assert model.prompts(cfg, toy, 5).shape == (2, 4, 8)


def test_the_session_is_laid_out_as_the_step_holds_it():
    """`lay_out`: a pair's two heads side by side, a ring's position p
    in slot p mod window, the scan's state entries by channels, each
    document once a question."""
    cfg, workload, model = _toy()
    driver = LOOKUP.module("drivers", workload["driver"])
    run = types.SimpleNamespace(config=cfg, workload=dict(workload,
                                                          window=4))
    rs = np.random.RandomState(0)
    length, docs = 10, 2
    made = {0: (rs.randn(docs, 128, 4).astype("float32"),
                rs.randn(docs, 3, 128).astype("float32")),
            1: tuple(rs.randn(docs, 8, 2, 16).astype("float32")
                     for _ in "kv"),
            5: tuple(rs.randn(docs, 64, 2, 16).astype("float32")
                     for _ in "kv")}
    init = driver.lay_out(run, model, made, length)
    assert init["pos"].tolist() == [length] * 4
    assert init["ssm_state_0"].shape == (4, 4, 128)
    assert np.array_equal(init["ssm_state_0"][2], made[0][0][1].T)
    assert np.array_equal(init["conv_tail_0"][1], made[0][1][0])
    assert init["k_ring_1"].shape == (4, 1, 4, 32)
    # the reference's ring holds positions 2 .. 9 in order; a ring of 4
    # keeps 6 .. 9, position p in slot p mod 4
    for p in range(6, 10):
        assert np.array_equal(init["k_ring_1"][3, 0, p % 4],
                              made[1][0][1, p - 2].reshape(32))
    assert init["v_cache_5"].shape == (4, 1, 64, 32)
    assert np.array_equal(init["v_cache_5"][0, 0, 7],
                          made[5][1][0, 7].reshape(32))


# -- the arithmetic ------------------------------------------------------------------

def test_parameters_and_bytes_are_the_issues():
    cfg = LOOKUP.json("configs", CONFIG)
    assert yoco.kinds(cfg) == LOOKUP.module(
        "models", "phi4flash_decode").kinds(cfg)
    assert [yoco.count(cfg, k) for k in ("mamba", "window", "full", "gmu",
                                         "cross")] == [9, 8, 1, 7, 7]
    assert yoco.widths(cfg) == (2560, 64, 5120, 16, 4, 160)
    d, f = 2560, 10240
    ffn = 3 * d * f
    assert ffn == 78643200                              # 78.6 M
    norms = 4 * d
    mamba = d * 10240 + 5120 * 4 + 5120 * 192 + 160 * 5120 + 5120 * d \
        + 5120 * (16 + 3)
    assert round(mamba / 1e6, 1) == 41.2
    assert sum(yoco.layer_parameters(cfg, "mamba")) == mamba + ffn + norms
    attn = 2 * d * d + d * 2 * 20 * 64 + 128 + 4 * 64
    assert round(attn / 1e6, 2) == 19.66
    assert sum(yoco.layer_parameters(cfg, "window")) == attn + ffn + norms
    assert sum(yoco.layer_parameters(cfg, "full")) == attn + ffn + norms
    cross = 2 * d * d + 128 + 4 * 64
    assert round(cross / 1e6, 2) == 13.11
    assert sum(yoco.layer_parameters(cfg, "cross")) == cross + ffn + norms
    gmu = 2 * d * 5120
    assert sum(yoco.layer_parameters(cfg, "gmu")) == gmu + ffn + norms
    total = 32 * (ffn + norms) + 9 * mamba + 9 * attn + 7 * cross \
        + 7 * gmu + 200064 * d + 2 * d
    assert yoco.chip_parameters(cfg) == total
    assert round(total / 1e9, 2) == 3.85
    assert round(yoco.weight_bytes(cfg, 2) / 1e9, 2) == 7.71
    # a token's keys and values: 5,120 B in one layer
    assert yoco.slot_bytes(cfg, 2) == 5120
    assert yoco.readers(cfg) == 8
    states = yoco.state_bytes(cfg, 16, 2)
    assert states == {"cache": 16 * 16384 * 5120,           # 1.34 GB
                      "ring": 8 * 16 * 512 * 5120,          # 0.34 GB
                      "state": 9 * 16 * 5120 * 16 * 4,      # 47 MB
                      "tail": 9 * 16 * 3 * 5120 * 2}        # 4.4 MB
    assert round(sum(states.values()) / 1e9, 2) == 1.73
    at = 15872 + 128 + 382 / 2.0
    shared = yoco.shared_kv_step(cfg, 16, at, 2)
    assert shared["bytes"] == 8 * 16 * (at + 1) * 5120
    assert round(shared["bytes"] / 1e9, 1) == 10.6
    # 40 heads: a 64-wide score and a 128-wide value a slot
    assert shared["flops"] == 8 * 16 * (at + 1) * 2 * 40 * (64 + 128)
    rings = yoco.window_step(cfg, 16, at, 2)
    assert rings["bytes"] == 8 * 16 * 512 * 5120
    assert yoco.window_step(cfg, 16, 99, 2)["bytes"] == 8 * 16 * 100 * 5120
    scan = yoco.scan_step(cfg, 16, 2)
    assert scan["bytes"] == 9 * (2 * 16 * 5120 * 16 * 4
                                 + 16 * (5120 * 8 + 2 * 16 * 4)
                                 + 5120 * 18 * 4)
    assert scan["flops"] == 9 * 16 * 5120 * 16 * 7
    assert yoco.tail_bytes(cfg, 16, 2) == 9 * 2 * 16 * 3 * 5120 * 2
    must = yoco.step_bytes(cfg, 16, at, 2, 2)
    assert must == yoco.weight_bytes(cfg, 2) + shared["bytes"] \
        + rings["bytes"] + scan["bytes"] + yoco.tail_bytes(cfg, 16, 2)
    assert round(must / 819e9 * 1e3, 1) == 22.9
    assert round(shared["bytes"] / must, 2) == 0.57


# -- the new readers ----------------------------------------------------------------

MARK = "~"
PATH = "jit(<lambda>)/while/body/closed_call/%s/~%s/%s"
FACTS = {"yoco_call_ms": 15000.0, "yoco_restore_ms": 400.0,
         "yoco_gen_len": 384, "yoco_prompt_len": 128,
         "yoco_session_len": 15872, "yoco_batch": 16, "yoco_calls": 2,
         "yoco_step_applications": 511, "yoco_traced_call_ms": 15100.0,
         "decode_trace_lower_s": 5.5, "setup_compile_s": 60.0,
         "setup_cache_misses": 30, "compiles_in_window": 0}


class Written(types.SimpleNamespace):
    """Hashable, as harness.Run is: some readers keep what they reduced
    by the run."""
    __hash__ = object.__hash__


def written_run(facts=FACTS, peaks=PEAKS, cell=CELL, config=CONFIG):
    """A run whose traced call spans 16 s: a prefill scan busy 1.8 of
    its 2 s, a decoding scan busy 12.5 of its 13.5: 0.25 s under a ring's
    `kv_write`, 0.5 in its kernel, 0.25 under the full layer's
    `kv_write`, 1 in its kernel, 4 in a cross layer's, 0.5 in that
    layer's `diff_combine`, 1 in a ring layer's, 2 in a scan, 1 in a
    convolution, 1 in a memory unit's product, 1 in another `mul`."""
    def op(start, end, name, category):
        return xplane.Op(start, end, name, category)

    ops = [op(0.5, 2.5, "while.3", "while"),
           op(0.6, 2.4, "gqa_decode_k2048_t128.9", "custom-call"),
           op(2.5, 16.0, "while.4", "while"),
           op(2.5, 2.75, "fusion.1", "loop fusion"),
           op(2.75, 3.25, "gqa_decode_w512.1", "custom-call"),
           op(3.25, 3.5, "fusion.2", "loop fusion"),
           op(3.5, 4.5, "gqa_decode_k2048.1", "custom-call"),
           op(4.5, 8.5, "gqa_decode_k2048.2", "custom-call"),
           op(8.5, 9.0, "fusion.3", "loop fusion"),
           op(9.0, 10.0, "fusion.4", "loop fusion"),
           op(10.0, 12.0, "fusion.5", "loop fusion"),
           op(12.0, 13.0, "fusion.6", "loop fusion"),
           op(13.0, 14.0, "fusion.8", "output fusion"),
           op(14.0, 15.0, "fusion.9", "output fusion")]
    trace = xplane.Trace({0: xplane.Device(ops, [(0.5, 16.0, "jit_fn")])},
                         [(0.0, 16.0, xplane.WINDOW_SPAN)])
    return Written(
        facts=dict(facts), peaks=peaks, reduced=trace, trace_dir=None,
        config=LOOKUP.json("configs", config),
        workload=LOOKUP.json("workloads", cell), lookup=LOOKUP, seed=5,
        trace=True, devices=[None])


def scoped_of(run, instances):
    ring, full, cross = (sorted(instances[kind][0])[0]
                         for kind in ("window", "full", "cross"))
    ring_combine, cross_combine = (sorted(instances[kind][1])[0]
                                   for kind in ("window", "cross"))
    paths = {
        "gqa_decode_k2048_t128.9": PATH % ("cached_attention", full[1:],
                                           "attn_full/pallas_call"),
        "fusion.1": PATH % ("cached_attention", ring[1:], "kv_write/dus"),
        "gqa_decode_w512.1": PATH % ("cached_attention", ring[1:],
                                     "attn_window/pallas_call"),
        "fusion.2": PATH % ("cached_attention", full[1:], "kv_write/dus"),
        "gqa_decode_k2048.1": PATH % ("cached_attention", full[1:],
                                      "attn_full/pallas_call"),
        "gqa_decode_k2048.2": PATH % ("cached_attention", cross[1:],
                                      "attn_cross/pallas_call"),
        "fusion.3": PATH % ("diff_combine", cross_combine[1:],
                            "diff_combine/mul"),
        "fusion.4": PATH % ("diff_combine", ring_combine[1:],
                            "diff_combine/mul"),
        "fusion.5": PATH % ("selective_scan", "selective_scan_0.tmp_0",
                            "exp"),
        "fusion.6": PATH % ("causal_conv1d", "causal_conv1d_0.tmp_0", "mul"),
        "fusion.8": PATH % ("mul", "gmu_18.tmp_0", "dot_general"),
        "fusion.9": PATH % ("mul", "fc_9.tmp_0", "dot_general"),
    }
    device = run.reduced.devices[0]
    return op_scopes.Scoped(
        [(o.start, o.end, o.name, paths.get(o.name, ""))
         for o in device.work], run.reduced.window)


def test_the_steps_attention_instances_by_kind():
    found = yoco_ops.attention_instances(written_run())
    assert [len(found[kind][0]) for kind in ("window", "full", "cross")] \
        == [8, 1, 7]
    assert [len(found[kind][1]) for kind in ("window", "full", "cross")] \
        == [8, 1, 7]


def test_the_new_readers_on_a_written_trace(monkeypatch, capsys):
    from paddle_tpu.obs import telemetry

    run = written_run()
    instances = yoco_ops.attention_instances(run)
    monkeypatch.setattr(
        yoco_ops, "operations",
        lambda r: (scoped_of(r, instances), MARK))
    # one lowered program: a block form of 128 positions, a step form
    monkeypatch.setattr(telemetry, "snapshot", lambda: {
        "decoder_positions_total{part=self}": 129,
        "decoder_positions_total{part=cross}": 2,
        "selective_scan_lowerings_total{form=block,state_dtype=float32}": 9,
        "selective_scan_lowerings_total{form=step,state_dtype=float32}": 9})
    read = {name: LOOKUP.module("layer_metrics", name).read(run)
            for name in NEW_READERS}
    steps = 383
    # inside the decoding scan alone: the full layer's kernel 1, the
    # cross layer's 4, its combine 0.5; not the kv_write, not the prefill
    assert read["shared_kv_attn_ms_per_step"] == pytest.approx(5500.0 / steps)
    assert read["diff_window_ms_per_step"] == pytest.approx(500.0 / steps)
    assert read["ssm_step_ms_per_step"] == pytest.approx(3000.0 / steps)
    assert read["gmu_ms_per_step"] == pytest.approx(1000.0 / steps)
    assert read["cross_positions_share"] == pytest.approx(100.0 / 128)
    cfg = run.config
    at = 15872 + 128 + 382 / 2.0
    shared = yoco.shared_kv_step(cfg, 16, at, 2)
    assert read["shared_kv_attn_roofline"] == pytest.approx(
        100.0 * shared["bytes"] / 819e9 / (5.5 / steps))
    scan = yoco.scan_step(cfg, 16, 2)
    assert read["ssm_step_roofline"] == pytest.approx(
        100.0 * scan["bytes"] / 819e9 / (2.0 / steps))
    must = yoco.step_bytes(cfg, 16, at, 2, 2)
    assert read["yoco_decode_hbm_roofline"] == pytest.approx(
        100.0 * must / 819e9 / (12.5 / steps))
    printed = capsys.readouterr().out
    assert "cross attn_cross %.4f" % (4000.0 / steps) in printed
    assert "full kv_write %.4f" % (250.0 / steps) in printed
    assert "cross diff_combine %.4f" % (500.0 / steps) in printed
    assert "attn_window %.4f, diff_combine %.4f, kv_write %.4f" % (
        500.0 / steps, 1000.0 / steps, 250.0 / steps) in printed
    assert "causal_conv1d %.4f, selective_scan %.4f" % (
        1000.0 / steps, 2000.0 / steps) in printed
    assert "a block ran 128.0 positions through the self-decoder and 1.0 " \
        "through the cross-decoder" in printed
    assert printed.count("(memory-bound)") == 2


def test_a_step_without_the_skip_reads_a_hundred(monkeypatch):
    from paddle_tpu.obs import telemetry

    monkeypatch.setattr(telemetry, "snapshot", lambda: {
        "decoder_positions_total{part=self}": 2 * 129,
        "decoder_positions_total{part=cross}": 2 * 129,
        "selective_scan_lowerings_total{form=block,state_dtype=float32}": 18,
        "selective_scan_lowerings_total{form=step,state_dtype=float32}": 18})
    reader = LOOKUP.module("layer_metrics", "cross_positions_share")
    assert reader.read(written_run()) == pytest.approx(100.0)
    monkeypatch.setattr(telemetry, "snapshot", lambda: {})
    assert reader.read(written_run()) is None


def test_the_new_readers_find_nothing_to_read_without_a_chip():
    run = written_run(peaks=None)
    for name in NEW_READERS:
        assert LOOKUP.module("layer_metrics", name).read(run) is None
    run = written_run({"yoco_call_ms": 15000.0})
    run.reduced = None
    for name in NEW_READERS:
        assert LOOKUP.module("layer_metrics", name).read(run) is None


# `data/phi4flash-turn-16k-steps.xplane.pb` is a recording from the chip
# (TPU v5 lite, this cell traced on --seed 6300000401, my chip run, PR
# 63, call 3) cut by benchmark/tests/cut_scan_recording.py to device 0's
# step 191 of the decoding scan's 383 under its `while` (1887 operations
# with their paths as the chip wrote them, `jit(<lambda>)/decode_steps/
# while/body/closed_call/cached_attention/~cached_attention_9.tmp_0/
# attn_cross/...gqa_decode_k2048/pallas_call`) and, as its other scan,
# one position of a `selective_scan` block's walk in the prefill (the
# question is one application, so the call's second longest `while` is
# a scan's 128 positions, 1.2 ms).  That decoding step wrote slot 15872
# + 128 + 191 = 16191, the mean of the call's decoding steps, so the
# facts below say one decoding step there and the floors are the whole
# call's.  (The run's `diff_combine` still opened a scope of its own
# name inside the op's: the readers go by the op's type.)  Of the whole
# scan the run itself printed, a decoding step: the shared cache's
# readers 14.2177 ms at 91.13% of their roofline, the rings' attention
# 0.8175, the scans 0.1921 at 65.85%, the memory units 0.5007, the step
# 25.4999 ms on the device, 89.86%.
RECORDED_FACTS = dict(yoco_gen_len=2, yoco_prompt_len=2,
                      yoco_session_len=16189, yoco_step_applications=2)
RECORDED_MS = {"shared_kv_attn_ms_per_step": 14.217255,
               "diff_window_ms_per_step": 0.816977,
               "ssm_step_ms_per_step": 0.242521,
               "gmu_ms_per_step": 0.499633}


def test_the_new_readers_on_a_recording_from_the_chip(tmp_path, capsys):
    import shutil

    shutil.copy(os.path.join(os.path.dirname(__file__), "data",
                             "phi4flash-turn-16k-steps.xplane.pb"),
                str(tmp_path))
    run = written_run(dict(FACTS, **RECORDED_FACTS))
    run.reduced, run.trace_dir = xplane.load(str(tmp_path)), str(tmp_path)
    read = {name: LOOKUP.module("layer_metrics", name).read(run)
            for name in NEW_READERS if name != "cross_positions_share"}
    for name, ms in RECORDED_MS.items():
        assert read[name] == pytest.approx(ms, abs=1e-6)
    printed = capsys.readouterr().out
    assert "cross attn_cross 12.4237, cross diff_combine 0.0155, full (no " \
        "scope) 0.0006, full attn_full 1.7753, full diff_combine 0.0022, " \
        "full kv_write 0.0080" in printed
    assert "(no scope) 0.0067, attn_window 0.8170, diff_combine 0.0189, " \
        "kv_write 0.0663" in printed
    assert "causal_conv1d 0.0497, selective_scan 0.1928" in printed
    assert "by op type: mul 0.4996" in printed
    cfg = run.config
    shared = yoco.shared_kv_step(cfg, 16, 16191.0, 2)
    assert read["shared_kv_attn_roofline"] == pytest.approx(
        100.0 * shared["bytes"] / 819e9 / 14.217255e-3, rel=1e-5)
    assert read["ssm_step_roofline"] == pytest.approx(
        100.0 * yoco.scan_step(cfg, 16, 2)["bytes"] / 819e9 / 0.192834e-3,
        rel=1e-4)
    assert "decode step: 25.5267 ms on the device" in printed
    must = yoco.step_bytes(cfg, 16, 16191.0, 2, 2)
    assert read["yoco_decode_hbm_roofline"] == pytest.approx(
        100.0 * must / 819e9 / 25.5267e-3, rel=1e-4)
    assert all(0 < read[n] < 100 for n in read if "roofline" in n)


@pytest.mark.parametrize("facts, cell, config", [
    ({"call_ms": 9000.0, "prefill_ms": 700.0, "gen_len": 512,
      "prompt_len": 512, "batch": 48, "traced_call_ms": 10000.0,
      "traced_step_applications": 1023, "decode_trace_lower_s": 2.5},
     "gpt2m-decode", "gpt2-medium"),
    ({"long_call_ms": 14400.0, "long_prefill_ms": 1900.0,
      "long_restore_ms": 210.0, "long_gen_len": 896,
      "long_prompt_len": 128, "long_session_len": 31744, "long_batch": 8,
      "long_step_applications": 1023, "decode_trace_lower_s": 5.5},
     "exaone-turn-32k-ep16", "k-exaone-236b-a23b"),
    ({"state_call_ms": 9000.0, "state_gen_len": 512,
      "state_prompt_len": 128, "state_batch": 128,
      "state_step_applications": 639, "decode_trace_lower_s": 4.3},
     "qwen3next-decode-ep16", "qwen3-next-80b-a3b")],
    ids=["gpt2m-decode", "exaone-turn-32k-ep16", "qwen3next-decode-ep16"])
def test_the_new_readers_find_nothing_on_the_other_generation_cells(
        facts, cell, config):
    """On the chip, traced, with the other drivers' facts (the parent's
    checkout with these files laid over it runs so): nothing, and no
    raise."""
    run = written_run(facts, cell=cell, config=config)
    for name in NEW_READERS:
        assert LOOKUP.module("layer_metrics", name).read(run) is None


def test_no_reader_of_the_benchmark_raises_on_this_cells_facts(monkeypatch):
    """Every reader under layer_metrics/, the other generation cells' and
    the training cells' among them, gives None or a number on this
    driver's facts with a chip's peaks set; the other generation cells'
    readers, whose counts would misstate this cell, find nothing to
    read."""
    run = written_run()
    run.trace_dir = os.path.join(CHECKOUT, "benchmark", "tests", "data")
    monkeypatch.setattr(yoco_ops, "operations", lambda r: None)
    found = {}
    for name in LOOKUP.names("layer_metrics"):
        if name in NEW_READERS:
            continue
        found[name] = LOOKUP.module("layer_metrics", name).read(run)
    assert all(v is None or isinstance(v, (int, float))
               for v in found.values()), found
    for name in ("share_decode_step_ms", "mla_ms_per_step",
                 "decode_step_ms", "prefill_ms_per_call",
                 "decode_hbm_roofline", "session_decode_step_ms",
                 "long_decode_step_ms", "long_decode_hbm_roofline",
                 "kv_attn_ms_per_step", "gqa_decode_roofline",
                 "gdn_ms_per_step", "gdn_step_roofline", "kda_ms_per_step",
                 "hybrid_decode_hbm_roofline", "reuse_decode_hbm_roofline",
                 "hc_ms_per_step", "dsa_ms_per_step", "mfu"):
        assert found[name] is None, name
    assert found["decode_trace_lower_s"] == 5.5
    assert found["setup_compile_s"] == 60.0
    assert found["setup_cache_misses"] == 30
    assert found["compiles_in_window"] == 0
    assert share_ops.operations(run) is None


# -- BENCHMARK.json ---------------------------------------------------------------

def test_benchmark_json_has_the_cell_and_its_metrics():
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    cell, workload = cells[CELL], LOOKUP.json("workloads", CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, CELL, 1)
    assert cell["why"] == workload["why"] and len(cell["why"]) <= 200
    # later cells come after: nothing here pins the lists' ends
    assert 16 <= len(cells) <= 24 and list(cells)[15] == CELL
    configs = {c["name"]: c for c in bench["configs"]}
    entry, config = configs[CONFIG], LOOKUP.json("configs", CONFIG)
    assert entry["file"] == "benchmark/configs/%s.json" % CONFIG
    assert entry["source"] == config["source"]
    assert entry["reduced"] == config["reduced"] == []
    assert len(entry["why"]) <= 200 and len(configs) >= 14
    end_to_end = {m["name"]: m for m in bench["end_to_end"]}
    assert CELL in end_to_end["decode_tok_per_s"]["workloads"]
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name in SHARED_READERS:
        assert CELL in listed[name]["workloads"], name
    for name, m in listed.items():
        if name not in NEW_READERS + SHARED_READERS:
            assert CELL not in m.get("workloads", []), name
    assert [m["name"] for m in bench["per_layer"]][105:113] \
        == list(NEW_READERS)
    for name in NEW_READERS:
        reader = LOOKUP.module("layer_metrics", name)
        assert listed[name]["workloads"] == [CELL]
        assert (listed[name]["moves"], listed[name]["layer"],
                listed[name]["unit"], listed[name]["source"]) == \
            (reader.MOVES, reader.LAYER, reader.UNIT, reader.SOURCE)
        assert set(listed[name]) == {"name", "unit", "better", "source",
                                     "layer", "moves", "workloads"}
        assert listed[name]["better"] == (
            "higher" if name.endswith("roofline") else "lower")


def test_the_configuration_keeps_every_published_key():
    """Every key of the catalog's entry under its own name and value,
    nothing reduced; what config.json does not carry is under `assumed`
    with its basis."""
    config = LOOKUP.json("configs", CONFIG)
    published = {
        "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
        "intermediate_size": 10240, "layer_norm_eps": 1e-05,
        "max_position_embeddings": 262144, "mb_per_layer": 2,
        "model_type": "phi4flash", "num_attention_heads": 40,
        "num_hidden_layers": 32, "num_key_value_heads": 20,
        "resid_pdrop": 0, "sliding_window": 512,
        "tie_word_embeddings": True, "mlp_bias": False,
        "lm_head_bias": False, "vocab_size": 200064}
    assert {k: config[k] for k in published} == published
    assert config["reduced"] == []
    assert (config["mamba_d_state"], config["mamba_d_conv"],
            config["mamba_expand"], config["mamba_dt_rank"]) == \
        (16, 4, 2, 160)
    for key in ("stands_for", "assumed", "departures", "arithmetic",
                "reduced_why", "source_part"):
        assert config[key]
    assert {"mamba_d_state", "mamba_d_conv", "mamba_expand",
            "mamba_dt_rank", "mamba_shapes", "projection_bias",
            "lambda_init", "differential_everywhere", "subln_eps",
            "positions", "window_edge", "memory", "block_order"} \
        <= set(config["assumed"])
    assert "whole" in config["stands_for"].lower()
    workload = LOOKUP.json("workloads", CELL)
    assert workload["session_len"] + workload["prompt_len"] \
        + workload["gen_len"] == config["serve_positions"] == 16384
    assert (workload["batch"], workload["documents"],
            workload["questions_a_document"], workload["session_len"],
            workload["prompt_len"], workload["gen_len"], workload["pool"],
            workload["checked_rows"]) == (16, 4, 4, 15872, 128, 384, 4, 2)
    assert (workload["serve_dtype"], workload["weights"]["dtype"],
            workload["state_dtype"]) == ("bfloat16", "bfloat16", "float32")
    assert workload["control"] == {"subtract": True,
                                   "memory_after_gate": False,
                                   "cross_before_write": False}
    assert set(workload["correct"]) == set(LIMITED) | {"why"}
    model = LOOKUP.module("models", "phi4flash_decode")
    sizes = model.sizes(config)
    assert (sizes["n_head"], sizes["n_kv_head"], sizes["d_head"],
            sizes["window"], sizes["d_state"], sizes["dt_rank"]) == \
        (40, 20, 64, 512, 16, 160)
    shapes = model.state_shapes(config, 16)
    assert shapes["k_ring_1"] == (16, 10, 512, 128)
    assert shapes["v_cache_17"] == (16, 10, 16384, 128)
    assert shapes["ssm_state_16"] == (16, 16, 5120)
    assert shapes["conv_tail_0"] == (16, 3, 5120)
    assert len(shapes) == 2 * 9 + 2 * 8 + 2
