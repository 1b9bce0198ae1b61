"""The reuse cell `hy4-turn-32k-ep16`: its driver end to end as a CPU
rehearsal at a toy size (fixture `hy4-tiny-turn`, found through
`--search-path`), the six controls that `correct` has to refuse, the
cell's copy of the reference against the program's own, the bytes and
operations of a decode step against counts made by hand, the new readers
on a written trace and on a recording cut from the builder's own traced
run, every reader the benchmark already had on this cell's facts with a
chip's peaks set, the configuration against the catalog's row, and
BENCHMARK.json's entries for the cell.
"""

import json
import os
import shutil
import types

import pytest

from benchmark.harness import CHECKOUT, Lookup
from benchmark.reduce import op_scopes, reuse_ops, xplane
from benchmark.tests import reuse_control
from benchmark.tests.test_run import FIXTURE, last_line, run_cell

CELL = "hy4-turn-32k-ep16"
CONFIG = "hy4-preview"
TOY = "hy4-tiny-turn"
NEW_READERS = ("hc_ms_per_step", "reuse_index_roofline",
               "index_reuse_share", "reuse_decode_hbm_roofline")
JOINED = ("decode_trace_lower_s", "decoder_prep_ms_per_call",
          "decoder_idle_ms_per_call", "prefill_device_ms_per_call",
          "decode_device_step_ms", "decode_unscoped_ms_per_step")
# (the control, a number it has to push past its limit)
CONTROLS = (("serve_dtype=float8_e4m3fn", "attn_off_first"),
            ("index_dtype=float8_e4m3fn", "selected_share"),
            ("control.gated=false", "attn_off"),
            ("control.sink=false", "attn_off"),
            ("control.hc_iterations=1", "mix_off"),
            (reuse_control.RECENT, "shared_off"))
FLOORS = ("selected_share",)
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
LOOKUP = Lookup([FIXTURE])
reuse_latent = LOOKUP.module("flops", "reuse_latent")
sparse_latent = LOOKUP.module("flops", "sparse_latent")


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
    # what only a chip can say: this cell's and the sibling cell's
    assert not (set(NEW_READERS) | {
        "dsa_ms_per_step", "dsa_index_roofline", "dsa_attend_roofline",
        "session_decode_hbm_roofline", "session_moe_ms_per_step"}) \
        & set(metrics)
    for stream in (proc.stdout, proc.stderr):
        for name in ("gap_mean", "selected_share", "attn_off",
                     "attn_off_first", "shared_off", "mix_off",
                     "held_part_off"):
            assert "check ok  : %s" % name in stream
    assert "index_cache_0, index_cache_1, latent_cache_0" in proc.stdout
    assert "index_cache_2" not in proc.stdout


# -- what `correct` has to refuse -----------------------------------------------

def _limits(workload):
    limits = workload["correct"]
    return limits, sorted(set(limits) - {"why"})


def _kept(got, limits, name):
    return got[name] >= limits[name] if name in FLOORS \
        else got[name] <= limits[name]


@pytest.fixture(scope="module")
def toy_reads():
    """{seed: `reuse_control.reader`'s read} at the toy size: one session
    a seed, as the control script makes it."""
    import jax

    workload = dict(LOOKUP.json("workloads", TOY), name=TOY)
    made = {}

    def read(seed, control=None):
        if seed not in made:
            made[seed] = reuse_control.reader(
                LOOKUP, workload, seed, jax.devices()[:1], None)
        return made[seed](control)

    return workload, read


@pytest.mark.parametrize("seed", [5, 6])
def test_the_sound_path_keeps_the_limits(toy_reads, seed):
    workload, read = toy_reads
    limits, names = _limits(workload)
    sound = read(seed)
    assert all(_kept(sound, limits, n) for n in names), sound
    assert sound["rows"] == workload["checked_rows"]
    assert sound["tokens"] == workload["checked_rows"] * workload["gen_len"]
    # four layers of which the first two choose; three expert layers
    assert len(sound["selected_share_by_layer"]) == 2
    assert len(sound["attn_off_by_layer"]) == 4 == \
        len(sound["mix_off_by_layer"])
    assert len(sound["held_part_off_by_layer"]) == 3


@pytest.mark.parametrize("control,seen_by", CONTROLS,
                         ids=[c[0].split(" ")[0] + c[0][-8:]
                              for c in CONTROLS])
def test_the_control_is_not_correct(toy_reads, control, seen_by):
    """The program's own path with a float8 latent cache, with the index
    keys cached in float8, without the gate, without the sink, with one
    Sinkhorn iteration, and with the most recent slots in place of the
    inherited set, each fail a limit that the cell as stated keeps."""
    workload, read = toy_reads
    limits, names = _limits(workload)
    got = read(5, control)
    assert not all(_kept(got, limits, n) for n in names), got
    assert not _kept(got, limits, seen_by), (seen_by, got)
    if control == reuse_control.RECENT:
        # the layers that choose are as sound as ever
        assert got["attn_off_by_layer"][0] <= limits["attn_off_first"]
        assert got["attn_off_by_layer"][1] <= limits["attn_off"]
        assert got["selected_share"] >= limits["selected_share"]
    if control == "control.hc_iterations=1":
        assert got["attn_off_first"] <= limits["attn_off_first"]


def test_the_recent_control_touches_the_inheriting_layers_alone():
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.ops import registry

    rs = np.random.RandomState(0)
    chosen = jnp.asarray([[0, 1, 5, 9]] * 2, jnp.int32)
    ins = {"QNope": [jnp.asarray(rs.randn(2, 1, 8), jnp.float32)],
           "QRope": [jnp.asarray(rs.randn(2, 1, 4), jnp.float32)],
           "CNew": [jnp.asarray(rs.randn(2, 1, 3), jnp.float32)],
           "RNew": [jnp.asarray(rs.randn(2, 1, 2), jnp.float32)],
           "Cache": [jnp.asarray(rs.randn(2, 12, 5), jnp.float32)],
           "WUk": [jnp.asarray(rs.randn(3, 8), jnp.float32)],
           "WUv": [jnp.asarray(rs.randn(3, 8), jnp.float32)],
           "Position": [jnp.full((2,), 9, jnp.int32)],
           "Selected": [chosen], "Live": [jnp.full((2,), 4, jnp.int32)]}
    info = registry.get_op_info("mla_cached_attention")
    real = info.kernel
    want = real(None, ins, {"num_heads": 2})["Out"][0]
    recent = real(None, dict(ins, Selected=[jnp.asarray(
        [[9, 8, 7, 6]] * 2, jnp.int32)]), {"num_heads": 2})["Out"][0]
    with reuse_control.inheriting_layers_attend_recent_slots(
            ["full", "shared"]):
        outs = [info.kernel(None, ins, {"num_heads": 2})["Out"][0]
                for _ in range(4)]      # two traces of two layers
    for got, ref in zip(outs, (want, recent, want, recent)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    assert info.kernel is real


# -- the reference and the seeded weights ---------------------------------------

def test_the_cells_reference_is_the_programs_own():
    with open(LOOKUP.path("reference", "hy4_preview.py")) as f:
        here = f.read()
    with open(os.path.join(CHECKOUT, "paddle_tpu", "models", "reference",
                           "hy4_preview.py")) as f:
        assert f.read() == here
    assert "paddle_tpu" not in here.replace(
        "paddle_tpu/models/reference", "")
    assert 'default_matmul_precision("highest")' in here


def test_the_weights_draw():
    """The kinds this model adds are float32 whatever the served type; a
    layer that inherits its set draws no index weights; a block made
    alone is the block served."""
    import jax
    import numpy as np

    cfg = LOOKUP.json("configs", "hy4-tiny")
    spec = dict(LOOKUP.json("workloads", TOY)["weights"], dtype="bfloat16")
    model = LOOKUP.module("models", "hy4_decode")
    key = jax.random.PRNGKey(7)
    tree = jax.jit(lambda k: model.weights(cfg, spec, k))(key)
    assert len(tree["blocks"]) == 4
    for i, block in enumerate(tree["blocks"]):
        assert ("w_ik" in block) == (i < 2)
        assert ("ffn_in" in block) == (i == 0)
        for name, value in block.items():
            float32 = name.startswith("hc_") or name in (
                "sink", "router_bias", "ik_norm_b")
            assert value.dtype == ("float32" if float32 else "bfloat16"), name
        assert block["hc_attn_p"].shape == (4 * 64, 24)
        assert abs(float(np.mean(block["sink"])) - spec["sink_mean"]) < 0.2
        assert abs(float(np.mean(block["hc_mlp_a"])) - 1.0) < 0.3
        alone = jax.jit(lambda k: model.block(cfg, spec, model.root(k), i))(
            key)
        for name in block:
            np.testing.assert_array_equal(np.asarray(alone[name]),
                                          np.asarray(block[name]))
    built = model.build(cfg, 4)
    assert sorted(built["cache_shapes"]) == [
        "index_cache_0", "index_cache_1", "latent_cache_0",
        "latent_cache_1", "latent_cache_2", "latent_cache_3"]
    # a layer that inherits carries the Variable of the layer it reads
    selected = [pairs["selected"][1] for _, pairs in built["probes"]]
    assert selected[1] == selected[2] == selected[3] != selected[0]


# -- the bytes and operations a step requires -----------------------------------

def test_step_bytes_by_hand():
    cfg = {"hidden_size": 8, "num_attention_heads": 2, "q_lora_rank": 4,
           "kv_lora_rank": 3, "qk_nope_head_dim": 2, "qk_rope_head_dim": 2,
           "v_head_dim": 2, "intermediate_size": 16,
           "moe_intermediate_size": 4, "scored_experts": 8,
           "n_routed_experts": 2, "num_experts_per_tok": 2,
           "num_hidden_layers": 3, "vocab_size": 10, "index_n_heads": 2,
           "index_head_dim": 4, "index_topk": 4, "hc_mult": 2,
           "indexer_types": ["full", "shared", "shared", "full"],
           "mlp_layer_types": ["dense", "sparse", "sparse", "sparse"]}
    assert reuse_latent.choosing_layers(cfg) == 1
    # attention: input norm 8, W_dq 32, q norm 4, W_uq 4 x 2 x 4 = 32,
    # W_dkv 8 x 5 = 40, kv norm 3, W_uk + W_uv 3 x 2 x 4 = 24, the gate
    # 8 x 4 = 32, W_o 4 x 8 = 32
    assert reuse_latent.attention_parameters(cfg) == 207
    # the chooser: W_iq 4 x 8 = 32, W_ik 8 x 4 = 32, LayerNorm 8, W_w 16
    assert reuse_latent.chooser_parameters(cfg) == 88
    # a hyper-connection: 2 x 8 values by 2 x 2 + 2 x 2 maps, 3 scalars,
    # 8 biases
    assert reuse_latent.stream_parameters(cfg) == 16 * 8 + 3 + 8 == 139
    # float32: two of those and 2 sinks a layer, a bias of 8 on each of
    # the two expert layers
    assert reuse_latent.float32_parameters(cfg) == 3 * (278 + 2) + 16
    # served type: every layer the attention and the norm before the
    # feed-forward (8); one chooser; dense feed-forward 3 x 8 x 16; two
    # shared experts 3 x 8 x 4 with a router 8 x 8; the head: a norm 8
    # and 8 x 10; looked up: 3 token rows
    served = 3 * 215 + 88 + 384 + 2 * (96 + 64) + 88 + 24
    assert reuse_latent.fixed_weight_bytes(cfg, 3, 2) == \
        served * 2 + 856 * 4
    # slot 5: 6 live keys of 4 values, ONE layer, 3 rows; 2 heads
    assert reuse_latent.index_step(cfg, 3, 5, 2) == {
        "flops": 2 * 3 * 2 * 4 * 6, "bytes": 3 * 6 * 4 * 2}
    assert reuse_latent.index_step(cfg, 3, 5, 2)["flops"] * 3 == \
        sparse_latent.index_step(cfg, 3, 5, 2)["flops"]
    # every layer attends 4 chosen of the 6 live, whoever chose them
    assert reuse_latent.attend_step(cfg, 3, 5, 2) == {
        "flops": 3 * (2 * 3 * 2 * 5 * 4 + 2 * 3 * 2 * 3 * 4),
        "bytes": 3 * 3 * 4 * 5 * 2}
    assert reuse_latent.step_bytes(cfg, 3, 5, 2, 2, 2) == \
        served * 2 + 856 * 4 + 144 + 360


def test_step_bytes_of_the_cell():
    """The issue's arithmetic: 3.9 GB of weights outside the routed
    experts (1.0 of them the five gates), the index scores of two layers
    over 0.13 GB of keys where five layers' would be 0.33."""
    cfg = LOOKUP.json("configs", CONFIG)
    workload = LOOKUP.json("workloads", CELL)
    rows = workload["batch"]
    assert reuse_latent.choosing_layers(cfg) == 2
    assert reuse_latent.attention_parameters(cfg) == pytest.approx(
        265.67e6, rel=1e-3)
    assert reuse_latent.chooser_parameters(cfg) == pytest.approx(
        9.37e6, rel=1e-3)
    assert 2 * reuse_latent.stream_parameters(cfg) == pytest.approx(
        1.18e6, rel=2e-3)
    fixed = reuse_latent.fixed_weight_bytes(cfg, rows, 2)
    assert fixed == pytest.approx(3.90e9, rel=3e-3)
    assert 5 * 6144 * 64 * 256 * 2 == pytest.approx(1.0e9, rel=0.01)
    at = workload["session_len"] + workload["prompt_len"] \
        + (workload["gen_len"] - 2) / 2.0
    assert at == 32319.0
    index = reuse_latent.index_step(cfg, rows, at, 2)
    assert index["bytes"] == pytest.approx(0.132e9, rel=0.01)
    assert sparse_latent.index_step(cfg, rows, at, 2)["bytes"] \
        == pytest.approx(2.5 * index["bytes"])
    assert index["flops"] == 2 * 2 * 8 * 32 * 128 * 32320
    attend = reuse_latent.attend_step(cfg, rows, at, 2)
    assert attend["bytes"] == 5 * 8 * 2048 * 1152 == 94_371_840
    assert attend["flops"] == 5 * 2 * 8 * 64 * (576 + 512) * 2048
    assert reuse_latent.step_bytes(cfg, rows, at, 2, 2, 2) == \
        pytest.approx(4.12e9, rel=0.01)
    # a token's caches: 576 values x 5 layers + 128 x 2 layers, 2 B
    assert (sparse_latent.latent_width(cfg) * 5
            + cfg["index_head_dim"] * 2) * 2 == 6272
    # the parameters of the share: 8.90 GB in bfloat16
    layers = cfg["num_hidden_layers"]
    routed = (layers - 1) * 16 * 3 * 6144 * 2048
    embed = cfg["vocab_size"] * 6144
    total = fixed - rows * 6144 * 2 + embed * 2 + routed * 2
    assert total == pytest.approx(8.90e9, rel=3e-3)


# -- the readers ------------------------------------------------------------------

MARK = "~"
PATH = "jit(<lambda>)/while/body/closed_call/%s/~%s/%s"
FACTS = {"reuse_call_ms": 12500.0, "reuse_restore_ms": 400.0,
         "reuse_gen_len": 896, "reuse_prompt_len": 128,
         "reuse_session_len": 31744, "reuse_batch": 8, "reuse_calls": 2,
         "reuse_traced_call_ms": 12500.0, "reuse_step_applications": 1023,
         "decode_trace_lower_s": 4.3, "setup_compile_s": 75.0,
         "setup_cache_misses": 39, "compiles_in_window": 0,
         "memory_peak_bytes": 13_000_000_000, "decode_tok_per_s": 600.0}


class Written(types.SimpleNamespace):
    """Hashable, as harness.Run is: some readers keep what they reduced
    by the run."""
    __hash__ = object.__hash__


def written_run(facts=FACTS, peaks=PEAKS, cell=CELL, config=CONFIG):
    """A run whose traced call spans 13 s: a prefill scan busy 1.8 of its
    2 s, a decoding scan busy 10 of its 10.5: 1 s under `dsa_index` and
    0.5 under `dsa_select` in each of two choosers... (one instance each
    here: 1 + 0.5), attention in three instances, the hyper-connection's
    three ops 0.5, 0.25 and 0.75 s."""
    def op(start, end, name, category):
        return xplane.Op(start, end, name, category)

    ops = [op(0.5, 2.5, "while.3", "while"),
           op(0.6, 2.4, "fusion.1", "loop fusion"),
           op(2.5, 13.0, "while.4", "while"),
           op(2.5, 3.5, "fusion.2", "output fusion"),
           op(3.5, 4.0, "call.1", "custom-call"),
           op(4.0, 5.0, "fusion.3", "output fusion"),
           op(5.0, 6.0, "fusion.4", "output fusion"),
           op(6.0, 7.0, "fusion.5", "output fusion"),
           op(7.0, 7.5, "fusion.6", "loop fusion"),
           op(7.5, 7.75, "fusion.7", "loop fusion"),
           op(7.75, 8.5, "fusion.8", "loop fusion"),
           op(8.5, 12.5, "fusion.9", "output fusion")]
    trace = xplane.Trace({0: xplane.Device(ops, [(0.5, 13.0, "jit_fn")])},
                         [(0.0, 13.0, xplane.WINDOW_SPAN)])
    return Written(
        facts=dict(facts), peaks=peaks, reduced=trace, trace_dir=None,
        config=LOOKUP.json("configs", config),
        workload=LOOKUP.json("workloads", cell), lookup=LOOKUP, seed=5,
        trace=True, devices=[None])


def scoped_of(run):
    paths = {
        "fusion.1": PATH % ("mla_index_select", "i.tmp_0", "dsa_index/x"),
        "fusion.2": PATH % ("mla_index_select", "i.tmp_0",
                            "dsa_index/dot_general"),
        "call.1": PATH % ("mla_index_select", "i.tmp_0",
                          "dsa_select/pallas_call"),
        "fusion.3": PATH % ("mla_cached_attention", "a.tmp_0",
                            "mla_scores/dot_general"),
        "fusion.4": PATH % ("mla_cached_attention", "a.tmp_1",
                            "mla_scores/dot_general"),
        "fusion.5": PATH % ("mla_cached_attention", "a.tmp_2",
                            "dsa_gather/gather"),
        "fusion.6": PATH % ("hc_maps", "h.tmp_0",
                            "hyper_connection/dot_general"),
        "fusion.7": PATH % ("hc_pre", "h.tmp_3", "hyper_connection/mul"),
        "fusion.8": PATH % ("hc_post", "h.tmp_4", "hyper_connection/add"),
        "fusion.9": PATH % ("mul", "fc_9.tmp_0", "dot_general"),
    }
    device = run.reduced.devices[0]
    return op_scopes.Scoped(
        [(o.start, o.end, o.name, paths.get(o.name, ""))
         for o in device.work], run.reduced.window)


def test_the_new_readers_on_a_written_trace(monkeypatch, capsys):
    run = written_run()
    monkeypatch.setattr(reuse_ops, "operations",
                        lambda r: (scoped_of(r), MARK))
    read = {name: LOOKUP.module("layer_metrics", name).read(run)
            for name in NEW_READERS}
    # inside the decoding scan alone: 0.5 + 0.25 + 0.75 s
    assert read["hc_ms_per_step"] == pytest.approx(1500.0 / 895)
    # three attention instances, one chooser
    assert read["index_reuse_share"] == pytest.approx(100.0 * 2 / 3)
    cfg = run.config
    index = reuse_latent.index_step(cfg, 8, 32319.0, 2)
    # the decoding scan's second under `dsa_index`, not the prefill's 1.8
    assert read["reuse_index_roofline"] == pytest.approx(
        100.0 * index["flops"] / 197e12 / (1.0 / 895))
    must = reuse_latent.step_bytes(cfg, 8, 32319.0, 2, 2, 2)
    assert read["reuse_decode_hbm_roofline"] == pytest.approx(
        100.0 * must / 819e9 / (10.0 / 895))
    assert all(0 < read[n] < 100 for n in NEW_READERS)
    printed = capsys.readouterr().out
    assert "hc_maps %.4f, hc_post %.4f, hc_pre %.4f" \
        % (500.0 / 895, 750.0 / 895, 250.0 / 895) in printed
    assert "of 3 attention layers in a decoding step, 1 chose" in printed
    assert "on 2 layers" in printed
    assert "decode step: %.4f ms on the device (a prefill step %.4f)" \
        % (10000.0 / 895, 1800.0 / 127) in printed


def test_the_new_readers_find_nothing_to_read_without_a_chip():
    run = written_run(peaks=None)
    for name in NEW_READERS:
        assert LOOKUP.module("layer_metrics", name).read(run) is None


@pytest.mark.parametrize("facts, cell, config", [
    ({"session_call_ms": 17700.0, "session_prefill_ms": 2400.0,
      "session_restore_ms": 180.0, "session_gen_len": 896,
      "session_prompt_len": 128, "session_len": 15360, "session_batch": 16,
      "session_step_applications": 1023, "decode_trace_lower_s": 4.3},
     "dsv32-turn-16k-ep16", "deepseek-v3.2"),
    ({"sparse_call_ms": 11000.0, "sparse_gen_len": 896,
      "sparse_prompt_len": 128, "sparse_session_len": 64512,
      "sparse_batch": 8, "sparse_step_applications": 1023,
      "decode_trace_lower_s": 4.3},
     "keye-turn-64k-ep8", "keye-vl-2.0-30b-a3b")],
    ids=["dsv32-turn-16k-ep16", "keye-turn-64k-ep8"])
def test_the_new_readers_find_nothing_on_the_other_chooser_cells(
        facts, cell, config):
    """On the chip, traced, with the other drivers' facts (the parent's
    checkout with these files laid over it runs so): nothing, and no
    raise."""
    run = written_run(facts, cell=cell, config=config)
    for name in NEW_READERS:
        assert LOOKUP.module("layer_metrics", name).read(run) is None


def test_no_reader_of_the_benchmark_raises_on_this_cells_facts(monkeypatch):
    """Every reader under layer_metrics/ gives None or a number on the
    reuse driver's facts with a chip's peaks set; the sibling cells'
    readers, whose counts would overstate this cell (a chooser on every
    layer), find nothing to read."""
    run = written_run()
    run.trace_dir = os.path.join(CHECKOUT, "benchmark", "tests", "data")
    monkeypatch.setattr(reuse_ops, "operations", lambda r: None)
    found = {}
    for name in LOOKUP.names("layer_metrics"):
        if name in NEW_READERS:
            continue
        found[name] = LOOKUP.module("layer_metrics", name).read(run)
    assert all(v is None or isinstance(v, (int, float))
               for v in found.values()), found
    for name in ("dsa_ms_per_step", "dsa_select_ms_per_step",
                 "dsa_index_roofline", "dsa_attend_roofline",
                 "session_decode_step_ms", "session_moe_ms_per_step",
                 "session_decode_hbm_roofline", "sparse_kv_index_roofline",
                 "sparse_decode_hbm_roofline", "mla_decode_roofline",
                 "share_decode_hbm_roofline", "decode_hbm_roofline", "mfu"):
        assert found[name] is None, name
    assert found["decode_trace_lower_s"] == 4.3
    assert found["setup_compile_s"] == 75.0
    assert found["setup_cache_misses"] == 39
    assert found["compiles_in_window"] == 0


# -- BENCHMARK.json and the configuration -----------------------------------------

def test_benchmark_json_has_the_cell_and_its_metrics():
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    cell, workload = cells[CELL], LOOKUP.json("workloads", CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, CELL, 1)
    assert cell["why"] == workload["why"] and len(cell["why"]) <= 200
    assert 15 <= len(cells) <= 24
    assert sum(w["chips"] == 4 for w in cells.values()) == 1
    assert str(workload["weights"]["seed"]) in workload["weights"]["why"]
    configs = {c["name"]: c for c in bench["configs"]}
    entry, config = configs[CONFIG], LOOKUP.json("configs", CONFIG)
    assert entry["file"] == "benchmark/configs/%s.json" % CONFIG
    assert entry["source"] == config["source"]
    assert entry["reduced"] == config["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size",
        "num_nextn_predict_layers"]
    assert len(entry["why"]) <= 200
    end_to_end = {m["name"]: m for m in bench["end_to_end"]}
    assert CELL in end_to_end["decode_tok_per_s"]["workloads"]
    assert CELL not in end_to_end["train_items_per_s"]["workloads"]
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name in JOINED:
        assert CELL in listed[name]["workloads"]
    # a chooser on every layer is what those counts assume
    for name in ("dsa_index_roofline", "dsa_ms_per_step",
                 "session_decode_hbm_roofline", "dsa_attend_roofline",
                 "dsa_select_ms_per_step", "session_moe_ms_per_step"):
        assert CELL not in listed[name]["workloads"]
    for name in NEW_READERS:
        reader = LOOKUP.module("layer_metrics", name)
        assert listed[name]["workloads"] == [CELL]
        assert (listed[name]["moves"], listed[name]["layer"],
                listed[name]["unit"], listed[name]["source"]) == \
            (reader.MOVES, reader.LAYER, reader.UNIT, reader.SOURCE)
        assert set(listed[name]) == {"name", "unit", "better", "source",
                                     "layer", "moves", "workloads"}


def test_the_configuration_keeps_every_published_number():
    """Every key of the catalog's row under its own name and unchanged,
    the three per-layer lists whole; only the four reduced keys differ,
    and none of them is a width."""
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        row = [json.loads(line) for line in f
               if '"name": "Hy4-preview"' in line][0]
    config = LOOKUP.json("configs", CONFIG)
    published = row["config"]
    assert config["source"] == row["source_url"]
    differs = sorted(k for k, v in published.items() if config[k] != v)
    assert differs == sorted(config["reduced"])
    assert config["published"] == {k: published[k] for k in differs}
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"], config["num_nextn_predict_layers"]) \
        == (5, 16, 15104, 0)
    assert config["vocab_size"] * 8 == published["vocab_size"]
    assert config["scored_experts"] == published["n_routed_experts"] == 256
    assert len(config["indexer_types"]) == 78
    assert config["indexer_types"].count("full") == 21
    for key in ("stands_for", "assumed", "departures", "arithmetic",
                "reduced_why"):
        assert config[key]
    for said in ("16 chips share each expert layer",
                 "attention is data-parallel",
                 "vocabulary-parallel over 8"):
        assert said in config["stands_for"]
    model = LOOKUP.module("models", "hy4_decode")
    assert model.layer_kinds(config) == (
        ["full", "full", "shared", "shared", "shared"],
        ["dense", "sparse", "sparse", "sparse", "sparse"])
    sizes = model.sizes(config)
    assert sizes["indexer"] == (32, 128, 2048)
    assert (sizes["n_dense"], sizes["held"], sizes["n_experts"]) == \
        (1, (80, 16), 256)
    assert sizes["hc"] == {"streams": 4, "eps": 1e-6, "magnitude": 2.0,
                           "iterations": 20}
    assert (sizes["swiglu_limit"], sizes["routed_scale"],
            sizes["rope_theta"], sizes["eps"]) == (10.0, 2.827, 1e7, 1e-5)
    workload = LOOKUP.json("workloads", CELL)
    assert workload["session_len"] + workload["prompt_len"] \
        + workload["gen_len"] == config["serve_positions"] == 32768
    assert (workload["batch"], workload["documents"],
            workload["questions_a_document"], workload["session_len"],
            workload["prompt_len"], workload["gen_len"], workload["pool"],
            workload["checked_rows"]) == (8, 2, 4, 31744, 128, 896, 4, 2)
    assert (workload["serve_dtype"], workload["index_dtype"],
            workload["weights"]["dtype"]) == ("bfloat16",) * 3
    assert set(workload["correct"]) == {
        "gap_mean", "not_first_share", "selected_share", "attn_off",
        "attn_off_first", "shared_off", "mix_off", "held_part_off", "why"}


# `data/hy4-turn-32k-ep16-steps.xplane.pb` is a recording from the chip
# (TPU v5 lite, this cell traced on --seed 6100000102, my chip run, PR 61)
# cut by benchmark/tests/cut_scan_recording.py to device 0's step 63 of
# the prefill scan's 127 and step 447 of the decoding scan's 895, each
# under its scan's `while`: 3457 operations with their paths as the chip
# wrote them.  That decoding step wrote slot 31744 + 128 + 447 = 32319,
# the mean of the call's decoding steps, so the facts below say one
# decoding step there and the floors are the whole call's.  Of the whole
# scans the run itself printed, a decoding step: hc_maps 0.1447 ms,
# hc_post 0.0047 (0.034 with its casts: the reader takes them since), hc_pre
# 0.0077; `dsa_index` 0.058 ms on 2 layers (37.25%
# of its roofline); the step 8.2666 ms on the device, 60.92% of the HBM
# floor; of 5 attention layers 2 chose their own set.
RECORDED_FACTS = dict(reuse_gen_len=2, reuse_prompt_len=2,
                      reuse_session_len=32317, reuse_step_applications=2)


def test_the_new_readers_on_a_recording_from_the_chip(tmp_path, capsys):
    shutil.copy(os.path.join(os.path.dirname(__file__), "data",
                             "hy4-turn-32k-ep16-steps.xplane.pb"),
                str(tmp_path))
    run = written_run(dict(FACTS, **RECORDED_FACTS))
    run.reduced, run.trace_dir = xplane.load(str(tmp_path)), str(tmp_path)
    read = {name: LOOKUP.module("layer_metrics", name).read(run)
            for name in NEW_READERS}
    printed = capsys.readouterr().out
    # ten casts back to bfloat16 (0.0296 ms) carry `hc_post` and not the
    # scope in this recording: the reader takes an op's name too
    assert read["hc_ms_per_step"] == pytest.approx(0.187287, abs=1e-6)
    assert "hc_maps 0.1449, hc_post 0.0342, hc_pre 0.0081" in printed
    assert read["index_reuse_share"] == 60.0
    assert "of 5 attention layers in a decoding step, 2 chose" in printed
    index = reuse_latent.index_step(run.config, 8, 32319.0, 2)
    assert "dsa_index: 0.058 ms a decode step on the device, on 2 layers" \
        in printed
    assert read["reuse_index_roofline"] == pytest.approx(37.385, abs=1e-3)
    assert read["reuse_index_roofline"] == pytest.approx(
        100.0 * index["flops"] / 197e12 / 0.0575e-3, rel=0.01)
    # sparse_latent's count, a chooser on every layer, would read 93.5%
    # of a roofline the two choosers reach 37% of
    assert sparse_latent.index_step(run.config, 8, 32319.0, 2)["flops"] \
        == 2.5 * index["flops"]
    assert "decode step: 7.9463 ms on the device (a prefill step 8.1894)" \
        in printed
    must = reuse_latent.step_bytes(run.config, 8, 32319.0, 2, 2, 2)
    assert read["reuse_decode_hbm_roofline"] == pytest.approx(
        100.0 * must / 819e9 / 7.9463e-3, rel=1e-4)
    assert all(0 < read[n] < 100 for n in NEW_READERS)
