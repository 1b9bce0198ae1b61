"""The generation cell: its driver end to end as a CPU rehearsal at a toy
size (fixture `gpt2-tiny-decode`, found through `--search-path`), the
controls and the broken timed paths that `correct` has to refuse, the
bytes of a decode step against a count made by hand, the new readers on
a written trace, and BENCHMARK.json's entries for the cell.
"""

import json
import os
import types

import numpy as np
import pytest

from benchmark.harness import CHECKOUT, Lookup
from benchmark.reduce import op_instances, xplane
from benchmark.tests import decode_control
from benchmark.tests.test_run import FIXTURE, last_line, run_cell

CELL = "gpt2m-decode"
TOY = "gpt2-tiny-decode"
NEW_READERS = ("prefill_ms_per_call", "decode_step_ms",
               "decode_attention_ms_per_step", "decode_hbm_roofline",
               "decode_trace_lower_s")
TRAINING = ["resnet50-train", "resnet50-train-dp4", "gpt2m-train",
            "ouro-train-4k", "olmoe-train-4k", "granite-train-4k"]
LOWER = ("serve_dtype=float8_e4m3fn", "weights.dtype=float8_e4m3fn")
LOOKUP = Lookup([FIXTURE])
decode = LOOKUP.module("flops", "decode")


# -- the driver, end to end -----------------------------------------------------

def test_untraced_rehearsal_has_exactly_the_two_metrics():
    result = last_line(run_cell(TOY, 0))
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"decode_tok_per_s", "setup_s"}
    assert result["metrics"]["decode_tok_per_s"]["unit"] == "tok/s"
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
    assert metrics["decode_trace_lower_s"]["value"] > 0
    # no start-up program runs: the executor jits nothing to read
    assert "setup_trace_lower_s" not in metrics
    # what only a chip can say
    assert not {"prefill_ms_per_call", "decode_step_ms",
                "decode_attention_ms_per_step",
                "decode_hbm_roofline"} & set(metrics)
    # every number compared stands beside its limit, on both streams
    for stream in (proc.stdout, proc.stderr):
        assert "check ok  : gap_max" in stream and ", limit 0.125" in stream
        assert "check ok  : no compile inside the windows (0), limit 0" \
            in stream


# -- what `correct` has to refuse -----------------------------------------------

def rehearse(monkeypatch, capsys):
    """run.py's main in this process, past the look for a chip, on the
    toy cell: (result line, everything printed)."""
    from benchmark import run as run_py

    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert run_py.main(["--workload", TOY, "--seed", "5", "--seconds",
                        "0.2", "--trace", "0", "--search-path",
                        FIXTURE]) == 0
    out = capsys.readouterr().out
    return json.loads(out.strip().splitlines()[-1]), out


def test_the_sound_path_is_correct_in_process(monkeypatch, capsys):
    result, _ = rehearse(monkeypatch, capsys)
    assert result["correct"] is True


def test_a_token_altered_where_it_is_produced_is_not_correct(
        monkeypatch, capsys):
    from paddle_tpu.fluid import fast_decode

    real = fast_decode.greedy_decode

    def altered(*args, **kwargs):
        tokens, lengths = real(*args, **kwargs)
        return tokens.at[:, 7].set((tokens[:, 7] + 1) % 97), lengths

    monkeypatch.setattr(fast_decode, "greedy_decode", altered)
    result, out = rehearse(monkeypatch, capsys)
    assert result["correct"] is False
    assert "check FAIL: gap_max" in out


def test_a_position_dropped_from_the_cache_is_not_correct(
        monkeypatch, capsys):
    """The step attends the slots before the one it writes and not its
    own: every token is chosen without its own key and value."""
    import jax.numpy as jnp
    from paddle_tpu.ops import registry

    info = registry.get_op_info("cached_attention")
    real = info.kernel

    def dropped(ctx, ins, attrs):
        ins = dict(ins)
        ins["KNew"] = [jnp.zeros_like(ins["KNew"][0])]
        ins["VNew"] = [jnp.zeros_like(ins["VNew"][0])]
        return real(ctx, ins, attrs)

    monkeypatch.setattr(info, "kernel", dropped)
    result, out = rehearse(monkeypatch, capsys)
    assert result["correct"] is False
    assert "check FAIL" in out


@pytest.mark.parametrize("lower", LOWER)
@pytest.mark.parametrize("seed", [5, 6, 7])
def test_the_control_is_not_correct(seed, lower):
    """The program's own path in the type below the one the cell states
    (a float8 cache; float8 weights) fails a limit that the served
    tokens of the cell as stated keep."""
    import jax

    workload = dict(LOOKUP.json("workloads", TOY), name=TOY)
    limits = workload["correct"]
    names = sorted(set(limits) - {"why"})
    devices = jax.devices()[:1]
    sound = decode_control.read(LOOKUP, workload, seed, devices, None)
    lowered = decode_control.read(LOOKUP, workload, seed, devices, None,
                                  lower)
    assert all(sound[n] <= limits[n] for n in names)
    assert any(lowered[n] > limits[n] for n in names)
    assert sound["tokens"] == lowered["tokens"] == 4 * 40


def test_the_seeded_text_does_not_collapse():
    """With the cell's draw of the weights (sharp attention, no part of
    the feed-forward's output that every context shares) a call's tokens
    are varied: independent draws repeat a handful."""
    import jax

    workload = dict(LOOKUP.json("workloads", TOY), name=TOY)
    got = decode_control.read(LOOKUP, workload, 5, jax.devices()[:1], None)
    plain = decode_control.changed(
        decode_control.changed(workload, "weights.qk_gain=1"),
        "weights.paired_fc_2=false")
    was = decode_control.read(LOOKUP, plain, 5, jax.devices()[:1], None)
    assert got["distinct"] >= 40 > 2 * was["distinct"]


def test_the_weights_draw():
    import jax

    cfg = LOOKUP.json("configs", "gpt2-tiny")
    spec = dict(LOOKUP.json("workloads", TOY)["weights"], dtype="float32")
    model = LOOKUP.module("models", "gpt2_decode")
    key = jax.random.PRNGKey(3)
    tree = model.weights(cfg, spec, key)
    plain = model.weights(cfg, dict(spec, qk_gain=1.0, paired_fc_2=False),
                          key)
    width = cfg["n_embd"]
    for block, was in zip(tree["blocks"], plain["blocks"]):
        w, b = block["qkv"]
        np.testing.assert_allclose(w[:, :2 * width],
                                   4.0 * was["qkv"][0][:, :2 * width],
                                   rtol=1e-6)
        np.testing.assert_array_equal(w[:, 2 * width:],
                                      was["qkv"][0][:, 2 * width:])
        np.testing.assert_array_equal(b, was["qkv"][1])
        f = np.asarray(block["fc_2"][0])
        np.testing.assert_array_equal(f[:2 * width], -f[2 * width:])
        np.testing.assert_array_equal(f[:2 * width],
                                      was["fc_2"][0][:2 * width])


def test_the_reference_reads_no_gap_for_its_own_first_tokens():
    """`gaps` of the reference's own greedy tokens is 0 everywhere, and
    one altered token opens a gap at its position alone."""
    import jax
    import jax.numpy as jnp

    cfg = LOOKUP.json("configs", "gpt2-tiny")
    spec = LOOKUP.json("workloads", TOY)["weights"]
    model = LOOKUP.module("models", "gpt2_decode")
    reference = LOOKUP.module("reference", "gpt2_decode")
    params = model.weights(cfg, dict(spec, dtype="float32"),
                           jax.random.PRNGKey(3))
    prompt = jnp.asarray(np.arange(12).reshape(2, 6) % 97, jnp.int32)
    served = jnp.zeros((2, 0), jnp.int32)
    for _ in range(5):
        tokens = jnp.concatenate([prompt, served], axis=1)
        z = reference.logits(cfg, params, tokens, tokens.shape[1] - 1)
        served = jnp.concatenate(
            [served, jnp.argmax(z[:, 0], axis=-1)[:, None].astype(
                jnp.int32)], axis=1)
    assert float(reference.gaps(cfg, params, prompt, served).max()) == 0.0
    wrong = served.at[1, 2].set((served[1, 2] + 1) % 97)
    opened = np.asarray(reference.gaps(cfg, params, prompt, wrong))
    assert opened[1, 2] > 0 and opened[0].max() == 0 and \
        opened[1, :2].max() == 0


# -- the bytes a step must move ---------------------------------------------------

def test_decode_step_bytes_by_hand():
    cfg = {"n_embd": 8, "n_inner": None, "n_layer": 2, "vocab_size": 10,
           "n_positions": 16}
    # a block: two norms 4 x 8, qkv 8 x 24 + 24, proj 8 x 8 + 8, fc 8 x 32
    # + 32 and 32 x 8 + 8: 32 + 216 + 72 + 288 + 264 = 872; the head: a
    # norm 16, 8 x 10 + 10; looked up: 3 token rows and 1 position row
    assert decode.weight_bytes(cfg, 3, 2) == \
        (2 * 872 + 16 + 90 + 4 * 8) * 2 == 3764
    # slot 5: keys and values of 6 slots, 2 layers, 3 rows, 8 wide
    assert decode.cache_bytes(cfg, 3, 5, 2) == 2 * 2 * 3 * 8 * 6 * 2 == 1152
    assert decode.step_bytes(cfg, 3, 5, 2, 2) == 3764 + 1152
    assert decode.mean_step_bytes(cfg, 3, 4, 6, 2, 2) == 3764 + 1152
    assert decode.whole_extent_step_bytes(cfg, 3, 2, 2) == \
        3764 + 2 * 2 * 3 * 8 * 16 * 2


def test_decode_step_bytes_of_the_cell():
    cfg = LOOKUP.json("configs", "gpt2-medium")
    workload = LOOKUP.json("workloads", CELL)
    rows = workload["batch"]
    # 24 blocks of 12,596,224 parameters, the head 51,515,473, two bytes
    weights = decode.weight_bytes(cfg, rows, 2)
    assert weights == (24 * 12_596_224 + 51_515_473
                       + (rows + 1) * 1024) * 2
    # a sequence's whole cache: 24 layers x 2 x 1024 wide x 1024 slots
    assert decode.cache_bytes(cfg, 1, 1023, 2) == 100_663_296
    mean = decode.mean_step_bytes(cfg, rows, 512, 1022, 2, 2)
    assert mean == weights + rows * 98_304 * 768


# -- the readers ------------------------------------------------------------------

def written_run(facts, peaks={"hbm_bytes_per_s": 819e9}):
    """A run whose traced call spans 10 s, its device busy 8.5 of them:
    a copy, a prefill scan busy 3.5 of its 4 s, a decoding scan (a
    `while` of its own inside it) busy 4.6 of its 5."""
    ops = [xplane.Op(0.6, 1.0, "copy.9", "copy"),
           xplane.Op(1.0, 5.0, "while.3", "while"),
           xplane.Op(1.0, 4.5, "fusion.1", "loop fusion"),
           xplane.Op(5.0, 10.0, "while.4", "while"),
           xplane.Op(5.2, 9.8, "fusion.2", "output fusion"),
           xplane.Op(6.0, 7.0, "while.5", "while")]
    trace = xplane.Trace({0: xplane.Device(ops, [(1.0, 10.0, "jit_fn")])},
                         [(0.0, 10.0, xplane.WINDOW_SPAN)])
    return types.SimpleNamespace(
        facts=dict(facts), peaks=peaks, reduced=trace, trace_dir=None,
        config=LOOKUP.json("configs", "gpt2-medium"),
        workload=LOOKUP.json("workloads", CELL), lookup=LOOKUP)


FACTS = {"call_ms": 9000.0, "prefill_ms": 4400.0, "gen_len": 512,
         "prompt_len": 512, "batch": LOOKUP.json("workloads", CELL)["batch"], "traced_call_ms": 10000.0,
         "traced_step_applications": 1023, "decode_trace_lower_s": 2.5}


def test_the_new_readers_on_a_written_trace(monkeypatch, capsys):
    texts = {"fusion.1": "%fusion.1 = bf16[1000]{0} fusion(bf16[1000]{0} "
                         "%p), kind=kLoop",
             "fusion.2": "%fusion.2 = f32[500]{0} fusion(bf16[3000]{0} %q),"
                         " kind=kOutput"}
    monkeypatch.setattr(op_instances, "texts", lambda run: texts)
    run = written_run(FACTS)
    # (`decode_attention_ms_per_step` reads the trace's file itself)
    read = {name: LOOKUP.module("layer_metrics", name).read(run)
            for name in NEW_READERS if "attention" not in name}
    assert read["prefill_ms_per_call"] == 4400.0
    assert read["decode_step_ms"] == pytest.approx(4600.0 / 511)
    assert read["decode_trace_lower_s"] == 2.5
    rows = run.workload["batch"]
    must = decode.mean_step_bytes(run.config, rows, 512, 1022, 2, 2)
    # the device's own time inside the decoding scan, not the host's
    assert read["decode_hbm_roofline"] == pytest.approx(
        100.0 * must / 819e9 / (4.6 / 511))
    # the operations' own bytes: 2000 + 2000 and 2000 + 6000, the copy's
    # text unknown, the whiles left out, over 1023 step applications
    printed = capsys.readouterr().out
    assert "state %.3f GB a step application" % (12000 / 1023 / 1e9) \
        in printed
    assert "must move %.3f GB" % (must / 1e9) in printed
    assert "decode step: %.4f ms on the device (a prefill step %.4f)" \
        % (4600.0 / 511, 3500.0 / 511) in printed


def test_the_scans_of_a_call():
    from benchmark.reduce import scans

    device = written_run(FACTS).reduced.devices[0]
    assert scans.outermost(device, (0.0, 10.0)) == [(1.0, 5.0), (5.0, 10.0)]
    assert scans.outermost(device, (2.0, 10.0)) == [(5.0, 10.0)]
    assert scans.busy_seconds(device, (5.0, 10.0)) == pytest.approx(4.6)
    # a call that decodes nothing after its prefill has one scan: no
    # decode step to read
    run = written_run(FACTS)
    run.reduced.devices[0].ops[:] = [
        op for op in device.ops if op.name not in ("while.4", "while.5")]
    assert LOOKUP.module("layer_metrics",
                         "decode_hbm_roofline").read(run) is None


def test_an_op_type_under_the_scans_own_scopes():
    """The decoder's scan puts its scopes in front of `apply_op`'s: the
    type is the scope that holds the instance's, wherever that is."""
    reader = LOOKUP.module("layer_metrics", "decode_attention_ms_per_step")
    under = reader.type_under
    assert under("jit(<lambda>)/while/body/closed_call/cached_attention/"
                 "~cached_attention_3.tmp_0/dot_general:", "~") == \
        "cached_attention"
    assert under("jit(<lambda>)/while/body/closed_call/layer_norm/"
                 "~layer_norm_3.tmp_0/mul", "~") == "layer_norm"
    assert under("jit(segment_fn)/mul/~fc_0.tmp_0/dot_general", "~") == \
        "mul"
    # the scan's own slices and copies lie under no op instance
    assert under("jit(<lambda>)/while/body/dynamic_slice", "~") is None
    assert under("~alone", "~") is None


def test_the_new_readers_find_nothing_to_read_without_a_chip():
    run = written_run(FACTS, peaks=None)
    for name in set(NEW_READERS) - {"decode_trace_lower_s"}:
        assert LOOKUP.module("layer_metrics", name).read(run) is None
    run = written_run({"call_ms": 9000.0})
    run.reduced = None
    for name in NEW_READERS:
        assert LOOKUP.module("layer_metrics", name).read(run) is None


# -- BENCHMARK.json ---------------------------------------------------------------

def test_benchmark_json_has_the_cell_and_its_metrics():
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    cell, workload = cells[CELL], LOOKUP.json("workloads", CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("gpt2-medium", CELL, 1)
    assert cell["why"] == workload["why"] and len(cell["why"]) <= 200
    assert len(cells) <= 24
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(cells) // 4)
    end_to_end = {m["name"]: m for m in bench["end_to_end"]}
    rate = end_to_end["decode_tok_per_s"]
    # the cell is among the metric's, not all of them: later generation
    # cells report it too
    assert (rate["unit"], rate["better"], rate["source"]) == \
        ("tok/s", "higher", "host_clock") and CELL in rate["workloads"]
    assert 0.01 <= rate["bound"] <= 0.1
    assert "workloads" not in end_to_end["setup_s"]
    listed = {m["name"]: m for m in bench["per_layer"]}
    # the executor's trace-and-lower counter has nothing to read where no
    # start-up program runs: it lists the cells that have one
    assert listed["setup_trace_lower_s"]["workloads"] == TRAINING
    assert end_to_end["train_items_per_s"]["workloads"] == TRAINING
    assert "decode_idle_share" not in listed
    for name in NEW_READERS:
        reader = LOOKUP.module("layer_metrics", name)
        # this cell's alone, but for the one every generation cell reads
        cells_of = listed[name]["workloads"]
        assert cells_of == [CELL] or (
            name == "decode_trace_lower_s" and cells_of[0] == CELL)
        assert listed[name]["moves"] == reader.MOVES
        assert (listed[name]["layer"], listed[name]["unit"],
                listed[name]["source"]) == \
            (reader.LAYER, reader.UNIT, reader.SOURCE)
    # gpt2-medium's widths are untouched and nothing is reduced
    config = LOOKUP.json("configs", "gpt2-medium")
    assert (config["n_embd"], config["n_head"], config["n_layer"],
            config["n_positions"], config["vocab_size"],
            config["reduced"]) == (1024, 16, 24, 1024, 50257, [])
    assert workload["prompt_len"] + workload["gen_len"] == \
        config["n_positions"]
    limits = workload["correct"]
    assert set(limits) == {"gap_max", "gap_mean", "why"}
