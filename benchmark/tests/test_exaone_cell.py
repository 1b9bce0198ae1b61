"""The long-session cell `exaone-turn-32k-ep16`: its files found by name,
its driver end to end as a CPU rehearsal at a toy size (fixture
`exaone-tiny-turn`, found through `--search-path`), the four controls
that `correct` has to refuse, the cell's copy of the reference against
the program's own, the session it makes, the parameter and byte
arithmetic of flops/gqa_window.py against ISSUE 44's numbers, the new
readers on a written trace and on a recording from the chip, every reader the benchmark already had on
this cell's facts with a chip's peaks set, and BENCHMARK.json's entries
for the cell.
"""

import json
import os
import types

import numpy as np
import pytest

from benchmark.harness import CHECKOUT, Lookup
from benchmark.reduce import long_ops, op_scopes, session_ops, share_ops, \
    xplane
from benchmark.tests import long_control
from benchmark.tests.test_run import FIXTURE, last_line, run_cell

CELL = "exaone-turn-32k-ep16"
CONFIG = "k-exaone-236b-a23b"
TOY, TOY_CONFIG = "exaone-tiny-turn", "exaone-tiny"
NEW_READERS = ("kv_attn_ms_per_step", "kv_window_ms_per_step",
               "gqa_decode_roofline", "long_moe_ms_per_step",
               "long_decode_hbm_roofline", "long_decode_step_ms",
               "long_prefill_ms_per_call", "long_restore_ms_per_call")
LIMITED = ("gap_mean", "not_first_share", "attn_off_window",
           "attn_off_full", "attn_off_first", "held_part_off")
CONTROLS = ("serve_dtype=float8_e4m3fn", "window=4", long_control.RING_OFF,
            long_control.DROP_LAST)
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
LOOKUP = Lookup([FIXTURE])
gqa_window = LOOKUP.module("flops", "gqa_window")


# -- the cell's files, by name --------------------------------------------------

def test_the_cells_files_are_found_by_name():
    workload = LOOKUP.json("workloads", CELL)
    config = LOOKUP.json("configs", workload["config"])
    assert config["name"] == CONFIG
    for kind, name in (("drivers", workload["driver"]),
                       ("models", workload["builder"]),
                       ("reference", workload["reference"]),
                       ("flops", "gqa_window"), ("reduce", "long_ops")):
        assert os.path.dirname(LOOKUP.path(kind, name + ".py")).endswith(kind)
    assert set(NEW_READERS) <= set(LOOKUP.names("layer_metrics"))
    assert (config["builder"], config["reference"]) == \
        (workload["builder"], workload["reference"])


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
        "session_decode_step_ms", "session_moe_ms_per_step",
        "share_decode_step_ms", "mla_ms_per_step", "decode_step_ms",
        "decode_hbm_roofline"}) & set(metrics)
    for stream in (proc.stdout, proc.stderr):
        for name in LIMITED:
            assert "check ok  : %s" % name in stream
        assert "check ok  : no compile inside the windows (0), limit 0" \
            in stream
    assert "window caches" in proc.stdout and "full caches" in proc.stdout


@pytest.mark.parametrize("change, said", [
    (dict(gen_len=26), "do not fit 64 cache positions"),
    (dict(session_len=24), "not whole turns"),
])
def test_a_session_that_does_not_fit_is_refused_before_the_first_call(
        tmp_path, change, said):
    """`ProgramDecoder` cannot see a `pos` inside `init_state`: the
    driver answers for session + prompt + generated <= serve_positions,
    and the reference makes a session in whole turns."""
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

def _read(seed, control=None):
    import jax

    workload = dict(LOOKUP.json("workloads", TOY), name=TOY)
    return workload["correct"], long_control.read(
        LOOKUP, workload, seed, jax.devices()[:1], None, control)


@pytest.mark.parametrize("seed", [5, 6, 7])
def test_the_sound_path_keeps_the_limits(seed):
    limits, sound = _read(seed)
    assert set(limits) - {"why"} == set(LIMITED)
    assert all(sound[n] <= limits[n] for n in LIMITED), sound
    assert sound["rows"] == 2 and sound["tokens"] == 2 * 24
    assert len(sound["attn_off_by_layer"]) == 4
    assert len(sound["held_part_off_by_layer"]) == 3


@pytest.mark.parametrize("control", CONTROLS)
@pytest.mark.parametrize("seed", [5, 6])
def test_the_control_is_not_correct(seed, control):
    """Keys and values kept in float8, a window of half the slots, a ring
    that wraps a slot early and a token's last held expert dropped each
    fail a limit that the cell as stated keeps, and the limit that says
    which."""
    limits, got = _read(seed, control)
    assert not all(got[n] <= limits[n] for n in LIMITED), got
    if control == long_control.DROP_LAST:
        assert got["held_part_off"] > limits["held_part_off"]
    elif control == "serve_dtype=float8_e4m3fn":
        assert got["attn_off_full"] > limits["attn_off_full"]
        assert got["held_part_off"] <= limits["held_part_off"]
    else:
        # the first layer's ring holds no drift of the call's own
        assert got["attn_off_first"] > limits["attn_off_first"]
        assert got["attn_off_window"] > limits["attn_off_window"]


def test_a_constant_offset_of_the_rings_slots_is_no_fault():
    """What the ring control first was: every write a slot on.  Once the
    ring has wrapped it holds the same positions under other names, and
    the last step's window layers read as a sound run's."""
    def shifted():
        def off(real, ctx, ins, attrs):
            if attrs.get("window", 0):
                ins = dict(ins, Position=[ins["Position"][0] + 1])
            return real(ctx, ins, attrs)
        return long_control._kernel("cached_attention", off)

    long_control.FAULTS["shifted"] = shifted
    try:
        limits, got = _read(5, "shifted")
    finally:
        del long_control.FAULTS["shifted"]
    assert got["attn_off_first"] <= limits["attn_off_first"]


# -- the model: its draw, its reference, its session ------------------------------

def _toy():
    cfg = LOOKUP.json("configs", TOY_CONFIG)
    workload = LOOKUP.json("workloads", TOY)
    return cfg, workload, LOOKUP.module("models", "exaone_decode"), \
        LOOKUP.module("reference", "exaone_moe")


def test_the_weights_draw():
    import jax
    import jax.numpy as jnp

    cfg, workload, model, _ = _toy()
    spec = workload["weights"]
    key = jax.random.PRNGKey(9)
    tree = jax.jit(lambda k: model.weights(cfg, spec, k))(key)
    block = tree["blocks"][1]
    assert block["wq"].shape == (64, 64) and block["wk"].shape == (64, 32)
    assert block["q_norm"].shape == block["k_norm"].shape == (16,)
    assert block["router_bias"].dtype == jnp.float32
    assert block["w_gate"].shape == (4, 64, 32)
    assert "ffn_in" in tree["blocks"][0] and "router" not in tree["blocks"][0]
    # the queries' norm carries the gain; the keys' does not
    assert abs(float(jnp.mean(block["q_norm"])) - spec["qk_gain"]) < 0.3
    assert abs(float(jnp.mean(block["k_norm"])) - 1.0) < 0.1
    # a block made alone is bit for bit the block served
    alone = jax.jit(lambda k: model.block(cfg, spec, model.root(k), 1))(key)
    for name, value in alone.items():
        np.testing.assert_array_equal(np.asarray(value),
                                      np.asarray(block[name]))


def test_the_model_is_the_files_draw_and_the_traffic_is_the_seeds():
    cfg, _, model, _ = _toy()
    cell = LOOKUP.json("workloads", CELL)
    assert cell["weights"]["seed"] == 4400000501
    real = LOOKUP.json("configs", CONFIG)
    a, b = (model.documents(real, cell, seed) for seed in (1, 2 ** 31 + 5))
    assert a.shape == (2, 31744) and a.dtype == np.int32
    assert (a != b).any() and a.max() < 19200 and a.min() >= 0
    assert model.prompts(real, cell, 1).shape == (4, 8, 128)
    np.testing.assert_array_equal(a, model.documents(real, cell, 1))


@pytest.fixture(scope="module")
def toy_forward():
    """The toy model's forward over 2 documents of 32 + 32 tokens by the
    program's reference, and the cell's copy a turn at a time."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models.reference import exaone_moe as programs

    cfg, workload, model, reference = _toy()
    spec = workload["weights"]
    key = jax.random.PRNGKey(3)
    tree = jax.jit(lambda k: model.weights(cfg, spec, k))(key)
    tokens = np.random.default_rng(0).integers(0, 97, (2, 64),
                                               dtype=np.int32)
    want = programs.forward(cfg, tree, jnp.asarray(tokens),
                            held=(cfg["first_expert"], cfg["num_experts"]))
    root = model.root(key)
    layers = reference.Layers(cfg, 8)
    made, kept = reference.session(
        cfg, layers, model.ends(cfg, spec, root),
        lambda i: model.block(cfg, spec, root, i), tokens[:, :32], 32,
        keep={0, 1})
    return {"cfg": cfg, "spec": spec, "model": model, "root": root,
            "reference": reference, "programs": programs, "tree": tree,
            "tokens": tokens, "want": want, "layers": layers, "made": made,
            "kept": kept}


def test_the_session_is_what_the_programs_reference_caches(toy_forward):
    t = toy_forward
    found = t["programs"].forward(
        t["cfg"], t["tree"], t["tokens"][:, :32],
        held=(t["cfg"]["first_expert"], t["cfg"]["num_experts"]))
    want = t["programs"].session(t["cfg"], found, 64)
    for i, (keys, values) in enumerate(t["made"]):
        np.testing.assert_allclose(keys, want["k_cache_%d" % i], atol=2e-5)
        np.testing.assert_allclose(values, want["v_cache_%d" % i],
                                   atol=2e-5)
    assert t["made"][0][0].shape == (2, 2, 8, 16)       # a ring
    assert t["made"][3][0].shape == (2, 2, 64, 16)      # the extent


def test_the_turns_one_after_another_are_the_full_forward(toy_forward):
    """`gaps` continues from the session's own keys and values: the
    reference's greedy tokens read no gap, another token reads its own,
    and the last step's attention is the full forward's."""
    import jax.numpy as jnp

    t = toy_forward
    cfg, model, spec, root = t["cfg"], t["model"], t["spec"], t["root"]
    logits = np.asarray(t["want"]["logits"])
    # served token i of the turn was chosen from the logits at 32 + 7 + i
    served = np.argmax(logits[:, 39:63], axis=-1).astype(np.int32)
    served[0, 5] = (served[0, 5] + 1) % 97
    eps = cfg["rms_norm_eps"]
    last = {"at": 62, "attn_in": [
        np.asarray(t["programs"].rms_norm(
            x[:, 62], t["tree"]["blocks"][i]["input_norm"].astype(
                jnp.float32), eps))
        for i, x in enumerate([t["tree"]["embed"][t["tokens"]].astype(
            jnp.float32)] + t["want"]["hidden"][:-1])]}
    found, step = t["reference"].gaps(
        cfg, t["layers"], model.ends(cfg, spec, root),
        lambda i: model.block(cfg, spec, root, i), t["tokens"][:, 32:], 32,
        7, served, last, None, [t["kept"][0], t["kept"][1]])
    found = np.array(found)
    want_gap = logits[0, 44].max() - logits[0, 44, served[0, 5]]
    assert found.shape == (2, 24)
    assert found[0, 5] == pytest.approx(want_gap, abs=1e-4)
    found[0, 5] = 0
    assert np.abs(found).max() < 1e-5
    for i in range(4):
        want = np.asarray(t["want"]["attn"][i])[:, 62]
        got = np.stack(step["attn"][i])
        np.testing.assert_allclose(got, want, atol=1e-5 * np.abs(want).max()
                                   + 1e-6)


def test_the_held_part_of_a_step_whose_rows_chose_no_held_expert(
        toy_forward):
    t = toy_forward
    block = {k: np.asarray(v, np.float32)
             for k, v in t["tree"]["blocks"][1].items()}
    u = np.random.default_rng(1).normal(size=(4, 1, 64)).astype(np.float32)
    none = np.zeros((4, 2), np.int32)       # experts 0, 0: not held (2..5)
    zero = np.zeros_like(u)
    off = t["reference"].held_part_off
    assert off(t["cfg"], block, {"in": u, "idx": none, "out": zero}) == 0.0
    assert off(t["cfg"], block, {"in": u, "idx": none,
                                 "out": zero + 1}) == float("inf")
    held = np.full((4, 2), 3, np.int32)
    assert off(t["cfg"], block, {"in": u, "idx": held, "out": zero}) \
        == pytest.approx(1.0)


# -- the arithmetic, against ISSUE 44's numbers -----------------------------------

def test_parameters_and_bytes_are_the_issues():
    cfg = LOOKUP.json("configs", CONFIG)
    d = cfg["hidden_size"]
    weights = gqa_window.attention_parameters(cfg) - 2 * d - 2 * 128
    assert weights == 2 * d * 8192 + 2 * d * 1024           # 113.25M
    assert round(weights / 1e6, 2) == 113.25
    assert round(gqa_window.expert_parameters(cfg) / 1e6, 2) == 37.75
    assert round(gqa_window.dense_parameters(cfg) / 1e6, 2) == 339.74
    assert round(d * cfg["scored_experts"] / 1e6, 2) == 0.79
    assert round(cfg["published"]["vocab_size"] * d / 1e6, 1) == 943.7
    assert round(cfg["vocab_size"] * d / 1e6, 2) == 117.96
    chip = gqa_window.chip_parameters(cfg)
    assert round(chip / 1e9, 3) == 3.865 and round(chip * 2 / 1e9, 2) == 7.73
    assert "3.866B" in cfg["arithmetic"]["this_chip"]
    # caches: 4 KB a token a full layer, 512 KB a row a window layer
    assert gqa_window.slot_bytes(cfg, 2) == 4096
    assert gqa_window.slot_bytes(cfg, 2) * cfg["sliding_window"] == 512 * 1024
    session = gqa_window.session_bytes(cfg, 8, 2)
    assert session == {"full": 8 * 32768 * 2 * 4096,        # 2.15 GB
                       "window": 6 * 8 * 512 * 1024}        # 25 MB
    assert round(session["full"] / 1e9, 2) == 2.15
    # a step: 3.27 GB of weights outside the routed experts, 2.1 GB of
    # live keys and values, 2.6 ms of them at the HBM peak
    fixed = gqa_window.fixed_weight_bytes(cfg, 8, 2)
    assert round(fixed / 1e9, 2) == 3.27
    at = 31744 + 128 + 894 / 2.0
    live = gqa_window.kv_step(cfg, 8, at, 2)
    assert live["bytes"] == 8 * 4096 * (2 * (at + 1) + 6 * 128)
    assert round(live["bytes"] / 819e9 * 1e3, 1) == 2.6
    assert live["flops"] == 4 * 8 * 64 * 128 * (2 * (at + 1) + 6 * 128)
    assert gqa_window.step_bytes(cfg, 8, at, 2, 2) == fixed + live["bytes"]
    # the rings alone do not grow with the session
    assert gqa_window.kv_step(cfg, 8, 127, 2, ("window",)) == \
        gqa_window.kv_step(cfg, 8, at, 2, ("window",))
    assert gqa_window.live_slots(cfg, 5) == 8 * 6


# -- the new readers ----------------------------------------------------------------

MARK = "~"
PATH = "jit(<lambda>)/while/body/closed_call/%s/~%s/%s"
FACTS = {"long_call_ms": 14400.0, "long_prefill_ms": 1900.0,
         "long_restore_ms": 210.0, "long_gen_len": 896,
         "long_prompt_len": 128, "long_session_len": 31744,
         "long_batch": 8, "long_calls": 2, "long_step_applications": 1023,
         "long_traced_call_ms": 14500.0, "decode_trace_lower_s": 5.5,
         "setup_compile_s": 60.0, "setup_cache_misses": 30,
         "compiles_in_window": 0}


class Written(types.SimpleNamespace):
    """Hashable, as harness.Run is: some readers keep what they reduced
    by the run."""
    __hash__ = object.__hash__


def written_run(facts=FACTS, peaks=PEAKS, cell=CELL, config=CONFIG):
    """A run whose traced call spans 16 s: a prefill scan busy 1.8 of
    its 2 s, a decoding scan busy 12 of its 13.5: 0.25 s under a window
    layer's `kv_write`, 0.5 in its ring's kernel, 0.25 under a full
    layer's `kv_write`, 4 in its kernel, 4 in a grouped product, 1 in
    the router, 1 in the shared expert's product, 1 in another `mul`."""
    def op(start, end, name, category):
        return xplane.Op(start, end, name, category)

    ops = [op(0.5, 2.5, "while.3", "while"),
           op(0.6, 2.4, "gqa_decode_k2048.9", "custom-call"),
           op(2.5, 16.0, "while.4", "while"),
           op(2.5, 2.75, "fusion.1", "loop fusion"),
           op(2.75, 3.25, "gqa_decode_w128.1", "custom-call"),
           op(3.25, 3.5, "fusion.2", "loop fusion"),
           op(3.5, 7.5, "gqa_decode_k2048.1", "custom-call"),
           op(7.5, 11.5, "moe_gmm_fwd_m128_n1024_k64.1", "custom-call"),
           op(11.5, 12.5, "fusion.6", "output fusion"),
           op(12.5, 13.5, "fusion.8", "output fusion"),
           op(13.5, 14.5, "fusion.9", "output fusion")]
    trace = xplane.Trace({0: xplane.Device(ops, [(0.5, 16.0, "jit_fn")])},
                         [(0.0, 16.0, xplane.WINDOW_SPAN)])
    return Written(
        facts=dict(facts), peaks=peaks, reduced=trace, trace_dir=None,
        config=LOOKUP.json("configs", config),
        workload=LOOKUP.json("workloads", cell), lookup=LOOKUP, seed=5,
        trace=True, devices=[None])


def scoped_of(run, ring, full, shared):
    paths = {
        "gqa_decode_k2048.9": PATH % ("cached_attention", full[1:],
                                      "attn_full/pallas_call"),
        "fusion.1": PATH % ("cached_attention", ring[1:], "kv_write/dus"),
        "gqa_decode_w128.1": PATH % ("cached_attention", ring[1:],
                                     "attn_window/pallas_call"),
        "fusion.2": PATH % ("cached_attention", full[1:], "kv_write/dus"),
        "gqa_decode_k2048.1": PATH % ("cached_attention", full[1:],
                                      "attn_full/pallas_call"),
        "moe_gmm_fwd_m128_n1024_k64.1": PATH % (
            "moe_experts", "m.tmp_0", "moe_experts/pallas_call"),
        "fusion.6": PATH % ("moe_router", "r.tmp_0", "dot_general"),
        "fusion.8": PATH % ("mul", shared[1:], "dot_general"),
        "fusion.9": PATH % ("mul", "fc_9.tmp_0", "dot_general"),
    }
    device = run.reduced.devices[0]
    return op_scopes.Scoped(
        [(o.start, o.end, o.name, paths.get(o.name, ""))
         for o in device.work], run.reduced.window)


def _instances(run):
    attends = sorted(long_ops.instances(run, "cached_attention",
                                        lambda od: True))
    rings = sorted(long_ops.instances(
        run, "cached_attention", lambda od: od.attrs.get("window", 0)))
    shared = sorted(long_ops.instances(
        run, "mul", lambda od: od.input("Y")[0].endswith(
            ("shared_in", "shared_out"))))
    return attends, rings, shared


def test_the_new_readers_on_a_written_trace(monkeypatch, capsys):
    run = written_run()
    attends, rings, shared = _instances(run)
    # six rings and two extents; two products a shared expert, seven layers
    assert (len(attends), len(rings), len(shared)) == (8, 6, 14)
    full = sorted(set(attends) - set(rings))[0]
    monkeypatch.setattr(
        long_ops, "operations",
        lambda r: (scoped_of(r, rings[0], full, shared[0]), MARK))
    reader = {name: LOOKUP.module("layer_metrics", name)
              for name in NEW_READERS}
    read = {name: r.read(run) for name, r in reader.items()}
    assert read["long_prefill_ms_per_call"] == 1900.0
    assert read["long_restore_ms_per_call"] == 210.0
    assert read["long_decode_step_ms"] == pytest.approx(12500.0 / 895)
    # inside the decoding scan alone: 0.25 + 0.5 + 0.25 + 4 s, not the
    # prefill's 1.8 in the same kernel
    assert read["kv_attn_ms_per_step"] == pytest.approx(5000.0 / 895)
    assert read["kv_window_ms_per_step"] == pytest.approx(750.0 / 895)
    # router 1 + experts 4 + the shared expert's product 1, not the other
    assert read["long_moe_ms_per_step"] == pytest.approx(6000.0 / 895)
    cfg = run.config
    # the decode steps write slots 31872 .. 32766: mean 32319
    live = gqa_window.kv_step(cfg, 8, 32319.0, 2)
    assert read["gqa_decode_roofline"] == pytest.approx(
        100.0 * live["bytes"] / 819e9 / (4.5 / 895))
    must = gqa_window.step_bytes(cfg, 8, 32319.0, 2, 2)
    assert read["long_decode_hbm_roofline"] == pytest.approx(
        100.0 * must / 819e9 / (12.0 / 895))
    assert all(0 < read[n] < 100 for n in NEW_READERS if "roofline" in n)
    printed = capsys.readouterr().out
    assert "window attn_window %.4f" % (500.0 / 895) in printed
    assert "full kv_write %.4f" % (250.0 / 895) in printed
    assert "6 layers, %.4f ms a decoding step" % (750.0 / 895) in printed
    assert printed.count("(memory-bound)") == 2
    assert "decode step: %.4f ms on the device (a prefill step %.4f)" \
        % (12000.0 / 895, 1800.0 / 127) in printed


def test_the_new_readers_find_nothing_to_read_without_a_chip():
    run = written_run(peaks=None)
    for name in NEW_READERS:
        assert LOOKUP.module("layer_metrics", name).read(run) is None
    run = written_run({"long_call_ms": 14400.0})
    run.reduced = None
    for name in NEW_READERS:
        assert LOOKUP.module("layer_metrics", name).read(run) is None


# `data/exaone-turn-32k-ep16-steps.xplane.pb` is a recording from the chip
# (TPU v5 lite, this cell traced on --seed 4400000601, my chip run, PR 44)
# cut by benchmark/tests/cut_scan_recording.py to device 0's step 63 of
# the prefill scan's 127 and step 447 of the decoding scan's 895, each
# under its scan's `while`: 2505 operations with their paths as the chip
# wrote them (`jit(<lambda>)/while/body/closed_call/cached_attention/
# ~cached_attention_0.tmp_0/attn_window/cond/branch_0_fun/gqa_decode_w128/
# pallas_call`).  That decoding step wrote slot 31744 + 128 + 447 = 32319,
# the mean of the call's decoding steps, so the facts below say one
# decoding step there and the floors are the whole call's.  Of the whole
# scans the run itself printed, a decoding step: cached_attention 3.0514 ms
# (full attn_full 2.8394, window attn_window 0.1848), the rings' layers
# 0.2023, the kernels at 86.54% of their roofline, the expert layers
# 2.8575 (moe_experts 2.1373: this step's rows reached fewer held
# experts than the mean step's), the step 9.8718 ms on the device, 66.92%.
RECORDED_FACTS = dict(long_gen_len=2, long_prompt_len=2,
                      long_session_len=32317, long_step_applications=2)
RECORDED_MS = {"kv_attn_ms_per_step": 3.05101,
               "kv_window_ms_per_step": 0.201867,
               "long_moe_ms_per_step": 2.648648}


def test_the_new_readers_on_a_recording_from_the_chip(tmp_path, capsys):
    import shutil

    shutil.copy(os.path.join(os.path.dirname(__file__), "data",
                             "exaone-turn-32k-ep16-steps.xplane.pb"),
                str(tmp_path))
    run = written_run(dict(FACTS, **RECORDED_FACTS))
    run.reduced, run.trace_dir = xplane.load(str(tmp_path)), str(tmp_path)
    read = {name: LOOKUP.module("layer_metrics", name).read(run)
            for name in NEW_READERS}
    for name, ms in RECORDED_MS.items():
        assert read[name] == pytest.approx(ms, abs=1e-6)
    printed = capsys.readouterr().out
    assert "full (no scope) 0.0008, full attn_full 2.8394, full kv_write " \
        "0.0089, window (no scope) 0.0028, window attn_window 0.1850, " \
        "window kv_write 0.0141" in printed
    assert "moe_experts 1.9294, moe_router 0.0277, shared expert 0.6916" \
        in printed
    # the two kinds of kernel, by the names the chip gave them
    assert "gqa_decode_k* 2.8394 ms a step (x2), requires 2.118 GB" in printed
    assert "gqa_decode_w* 0.1850 ms a step (x6), requires 0.025 GB" in printed
    live = gqa_window.kv_step(run.config, 8, 32319.0, 2)
    assert read["gqa_decode_roofline"] == pytest.approx(
        100.0 * live["bytes"] / 819e9 / 3.02444e-3, rel=1e-4)
    assert "decode step: 9.6640 ms on the device (a prefill step 9.9784)" \
        in printed
    must = gqa_window.step_bytes(run.config, 8, 32319.0, 2, 2)
    assert read["long_decode_hbm_roofline"] == pytest.approx(
        100.0 * must / 819e9 / 9.6640e-3, rel=1e-4)
    assert all(0 < read[n] < 100 for n in NEW_READERS if "roofline" in n)


@pytest.mark.parametrize("facts, cell, config", [
    ({"call_ms": 9000.0, "prefill_ms": 700.0, "gen_len": 512,
      "prompt_len": 512, "batch": 48, "traced_call_ms": 10000.0,
      "traced_step_applications": 1023, "decode_trace_lower_s": 2.5},
     "gpt2m-decode", "gpt2-medium"),
    ({"share_call_ms": 30000.0, "share_prefill_ms": 3900.0,
      "share_gen_len": 896, "share_prompt_len": 128, "share_batch": 256,
      "share_step_applications": 1023, "decode_trace_lower_s": 3.4},
     "pangu-decode-ep16", "openpangu-ultra-moe-718b"),
    ({"session_call_ms": 17700.0, "session_prefill_ms": 2400.0,
      "session_restore_ms": 180.0, "session_gen_len": 896,
      "session_prompt_len": 128, "session_len": 15360, "session_batch": 16,
      "session_step_applications": 1023, "decode_trace_lower_s": 4.3},
     "dsv32-turn-16k-ep16", "deepseek-v3.2")],
    ids=["gpt2m-decode", "pangu-decode-ep16", "dsv32-turn-16k-ep16"])
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
    the training cells' among them, gives None or a number on the
    long-session driver's facts with a chip's peaks set; the other
    generation cells' readers, whose counts would misstate this cell,
    find nothing to read."""
    run = written_run()
    run.trace_dir = os.path.join(CHECKOUT, "benchmark", "tests", "data")
    monkeypatch.setattr(long_ops, "operations", lambda r: None)
    found = {}
    for name in LOOKUP.names("layer_metrics"):
        if name in NEW_READERS:
            continue
        found[name] = LOOKUP.module("layer_metrics", name).read(run)
    assert all(v is None or isinstance(v, (int, float))
               for v in found.values()), found
    for name in ("share_decode_step_ms", "share_prefill_ms_per_call",
                 "mla_ms_per_step", "mla_decode_roofline",
                 "moe_share_ms_per_step", "moe_share_roofline",
                 "share_decode_hbm_roofline", "decode_step_ms",
                 "prefill_ms_per_call", "decode_hbm_roofline",
                 "decode_attention_ms_per_step", "session_decode_step_ms",
                 "session_prefill_ms_per_call", "session_moe_ms_per_step",
                 "session_decode_hbm_roofline", "dsa_ms_per_step",
                 "moe_expert_roofline", "moe_ms_per_step", "mfu",
                 "setup_trace_lower_s"):
        assert found[name] is None, name
    assert found["decode_trace_lower_s"] == 5.5
    assert found["setup_compile_s"] == 60.0
    assert found["setup_cache_misses"] == 30
    assert found["compiles_in_window"] == 0
    assert share_ops.operations(run) is None
    assert session_ops.operations(run) is None


# -- BENCHMARK.json ---------------------------------------------------------------

def test_benchmark_json_has_the_cell_and_its_metrics():
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    cell, workload = cells[CELL], LOOKUP.json("workloads", CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, CELL, 1)
    assert cell["why"] == workload["why"] and len(cell["why"]) <= 200
    assert 10 <= len(cells) <= 24
    configs = {c["name"]: c for c in bench["configs"]}
    entry, config = configs[CONFIG], LOOKUP.json("configs", CONFIG)
    assert entry["file"] == "benchmark/configs/%s.json" % CONFIG
    assert entry["source"] == config["source"]
    assert entry["reduced"] == config["reduced"]
    assert len(entry["why"]) <= 200
    end_to_end = {m["name"]: m for m in bench["end_to_end"]}
    assert end_to_end["decode_tok_per_s"]["workloads"][:4] == \
        ["gpt2m-decode", "pangu-decode-ep16", "dsv32-turn-16k-ep16", CELL]
    listed = {m["name"]: m for m in bench["per_layer"]}
    assert listed["decode_trace_lower_s"]["workloads"][:4] == \
        ["gpt2m-decode", "pangu-decode-ep16", "dsv32-turn-16k-ep16", CELL]
    for name, m in listed.items():
        if name not in NEW_READERS and name != "decode_trace_lower_s":
            assert CELL not in m.get("workloads", []), name
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


def test_the_configuration_keeps_every_published_width():
    """Every key of the catalog's entry under its own name; only the
    seven reduced keys differ, and none of them is a width."""
    config = LOOKUP.json("configs", CONFIG)
    pattern = ["sliding_attention"] * 3 + ["full_attention"]
    published = {
        "first_k_dense_replace": 1, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 6144, "intermediate_size": 18432,
        "layer_types": pattern * 12, "max_position_embeddings": 262144,
        "mlp_layer_types": ["dense"] + ["sparse"] * 47,
        "model_type": "exaone_moe", "moe_intermediate_size": 2048,
        "mtp_layer_types": ["full_attention"], "mtp_sliding_windows": [0],
        "n_group": 1, "norm_topk_prob": True, "num_attention_heads": 64,
        "num_experts": 128, "num_experts_per_tok": 8,
        "num_hidden_layers": 48, "num_key_value_heads": 8,
        "num_nextn_predict_layers": 1, "num_shared_experts": 1,
        "rms_norm_eps": 1e-05,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
        "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
        "sliding_window": 128, "sliding_window_pattern": "LLLG",
        "sliding_windows": [128, 128, 128, 0] * 12,
        "tie_word_embeddings": False, "topk_group": 1,
        "vocab_size": 153600}
    differs = sorted(k for k, v in published.items() if config[k] != v)
    assert differs == sorted(config["reduced"])
    assert config["published"] == {k: published[k] for k in differs}
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"], config["num_nextn_predict_layers"]) == \
        (8, 8, 19200, 0)
    # two whole periods, the dense layer once and seven expert layers
    assert config["layer_types"] == pattern * 2
    assert config["sliding_windows"] == [128, 128, 128, 0] * 2
    assert config["mlp_layer_types"] == ["dense"] + ["sparse"] * 7
    assert config["scored_experts"] == 128
    assert 0 <= config["first_expert"] <= 120
    assert config["vocab_size"] * 8 == published["vocab_size"]
    for key in ("stands_for", "assumed", "departures", "arithmetic",
                "reduced_why"):
        assert config[key]
    assert {"block_order", "qk_norm", "rope_on_window_layers_only",
            "rope_layout", "window_edge", "router_bias",
            "router_dtype"} <= set(config["assumed"])
    workload = LOOKUP.json("workloads", CELL)
    assert workload["session_len"] + workload["prompt_len"] \
        + workload["gen_len"] == config["serve_positions"] == 32768
    assert (workload["batch"], workload["documents"],
            workload["questions_a_document"], workload["session_len"],
            workload["prompt_len"], workload["gen_len"], workload["pool"],
            workload["checked_rows"]) == (8, 2, 4, 31744, 128, 896, 4, 2)
    assert (workload["serve_dtype"], workload["weights"]["dtype"]) == \
        ("bfloat16",) * 2
    assert set(workload["correct"]) == set(LIMITED) | {"why"}
    model = LOOKUP.module("models", "exaone_decode")
    sizes = model.sizes(config)
    assert (sizes["n_head"], sizes["n_kv_head"], sizes["d_head"],
            sizes["window"], sizes["held"], sizes["n_experts"]) == \
        (64, 8, 128, 128, (config["first_expert"], 8), 128)
    shapes = model.cache_shapes(config, 8)
    assert shapes["k_cache_0"] == (8, 8, 128, 128)
    assert shapes["v_cache_3"] == (8, 8, 32768, 128)
    assert len(shapes) == 16
