"""The decoding layer's five readers (benchmark/reduce/decoder_trace.py),
against hand-computed answers on a written trace, on a recording from
the chip, and on a recording from before the program had the spans.

The written trace is one call of `decoder.greedy(prompt=<3 positions>,
max_len=3)` by a step that takes a position (`block` 1).  Microseconds:

    host: bench/window 0 .. 2200
            bench/generate 50 .. 2100
              decode/call 60 .. 2090
                decode/prep 70 .. 400
                decode/dispatch 410 .. 600
                decode/fetch 610 .. 2080
    device: one program (XLA Modules) 1000 .. 1900, in it
      copy.1      1000 .. 1010  no path (a parameter's copy)
      fusion.1    1010 .. 1050  decode_prefill/mul/~fc/...: the first
                                position, outside the scan
      while.4     1052 .. 1058  under decode_prefill (a search of its own)
        fusion.8  1053 .. 1057
      while.1     1060 .. 1260  decode_prefill/while: the prefill's scan
        fusion.2  1060 .. 1100, 1110 .. 1150
        fusion.3  1160 .. 1250
      fusion.9    1270 .. 1280  a path under decode_steps/while/body, moved
                                out of the loop by the compiler
      while.2     1300 .. 1800  decode_steps/while: two steps, each
        fusion.4  +0 .. +40     mul/~fc
        fusion.5  +40 .. +100   cached_attention/~att
        copy.7    +100 .. +120  no path
        fusion.6  +120 .. +130  decode_steps/while/body/argmax: no instance
                                (steps at 1300 and 1550)
      fusion.7    1810 .. 1850  jit(<lambda>)/transpose: neither scope
      fusion.10   1860 .. 1870  a path under decode_prefill, scheduled after
                                the steps (only the call's results need it)

The device idles between programs 0 .. 1000 and 1900 .. 2200.  Cut at the
spans' edges: no span 0 .. 50 and 2100 .. 2200; bench/generate 50 .. 60
and 2090 .. 2100 (20); decode/call 60 .. 70, 400 .. 410, 600 .. 610,
2080 .. 2090 (40); decode/prep 330; decode/dispatch 190; decode/fetch
610 .. 1000 and 1900 .. 2080 (570): 1130 inside the call, and with
bench/generate's 20 the 1150 `xplane.idle_gaps` puts down to
bench/generate; 60 .. 1000 lie before the call's program.  Prefill:
1010 .. 1260 (fusion.10 comes after the steps began and is left out, 10
late), busy 40 + 4 + 40 + 40 + 90 = 214.
Steps: while.2 (fusion.9 lies in no `while`), busy 2 x 130 = 260, 130 a
step; under no instance 2 x (20 + 10) = 60, 30 a step.
"""

import os

import pytest

from benchmark.reduce import decoder_trace, program_spans, xplane

US = 1e-6
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
LAMBDA = "jit(<lambda>)/"
BODY = LAMBDA + "decode_steps/while/body/"
OPS = [     # (text, path, [(start us, length us)])
    ("%copy.1 = bf16[8,8]{1,0} copy(bf16[8,8]{0,1} %p.1)", "", [(1000, 10)]),
    ("%fusion.1 = bf16[4,8]{1,0} fusion(bf16[4,8]{1,0} %a), kind=kOutput",
     LAMBDA + "decode_prefill/mul/~fc_0.tmp_0/dot_general:", [(1010, 40)]),
    ("%while.4 = (s32[]) while((s32[]) %t.4)",
     LAMBDA + "decode_prefill/moe_experts/~moe_0.tmp_0/while:", [(1052, 6)]),
    ("%fusion.8 = s32[] fusion(s32[] %b), kind=kLoop",
     LAMBDA + "decode_prefill/moe_experts/~moe_0.tmp_0/while/body/add:",
     [(1053, 4)]),
    ("%while.1 = (s32[], bf16[4,8]{1,0}) while((s32[]) %t.1)",
     LAMBDA + "decode_prefill/while:", [(1060, 200)]),
    ("%fusion.2 = bf16[4,8]{1,0} fusion(bf16[4,8]{1,0} %c), kind=kOutput",
     LAMBDA + "decode_prefill/while/body/closed_call/mul/~fc_0.tmp_0/"
     "dot_general:", [(1060, 40), (1110, 40)]),
    ("%fusion.3 = bf16[4,8]{1,0} fusion(bf16[4,8]{1,0} %d), kind=kLoop",
     LAMBDA + "decode_prefill/while/body/closed_call/cached_attention/"
     "~att_0.tmp_0/mul:", [(1160, 90)]),
    ("%fusion.9 = f32[8,8]{1,0} fusion(bf16[8,8]{1,0} %w), kind=kLoop",
     BODY + "closed_call/mul/~fc_0.tmp_0/convert_element_type:",
     [(1270, 10)]),
    ("%while.2 = (s32[], bf16[4,8]{1,0}) while((s32[]) %t.2)",
     LAMBDA + "decode_steps/while:", [(1300, 500)]),
    ("%fusion.4 = bf16[4,8]{1,0} fusion(bf16[4,8]{1,0} %e), kind=kOutput",
     BODY + "closed_call/mul/~fc_0.tmp_0/dot_general:",
     [(1300, 40), (1550, 40)]),
    ("%fusion.5 = bf16[4,8]{1,0} fusion(bf16[4,8]{1,0} %f), kind=kLoop",
     BODY + "closed_call/cached_attention/~att_0.tmp_0/mul:",
     [(1340, 60), (1590, 60)]),
    ("%copy.7 = bf16[4,8]{1,0} copy(bf16[4,8]{0,1} %g)", "",
     [(1400, 20), (1650, 20)]),
    ("%fusion.6 = s32[4]{0} fusion(bf16[4,16]{1,0} %h), kind=kLoop",
     BODY + "argmax:", [(1420, 10), (1670, 10)]),
    ("%fusion.7 = s32[4,3]{1,0} fusion(s32[3,4]{1,0} %i), kind=kLoop",
     LAMBDA + "transpose:", [(1810, 40)]),
    ("%fusion.10 = bf16[4,8]{1,0} fusion(bf16[4,8]{1,0} %j), kind=kLoop",
     LAMBDA + "decode_prefill/while/body/closed_call/assign/~probe_0/copy:",
     [(1860, 10)]),
]
SPANS = [   # (name, start us, end us, {argument: value})
    ("bench/window", 0, 2200, {}),
    ("bench/generate", 50, 2100, {}),
    ("decode/call", 60, 2090, {"mode": "greedy-prefill", "call": 3,
                               "batch": 4, "prompt_len": 3, "max_len": 3,
                               "block": 1, "built": 0}),
    ("decode/prep", 70, 400, {"host_bytes": 128, "device_bytes": 0}),
    ("decode/dispatch", 410, 600, {}),
    ("decode/fetch", 610, 2080, {}),
]


def host_plane(spans, name="/host:CPU"):
    """The text of a host plane whose one line holds `spans`, each
    argument a stat of the event (as `TraceAnnotation` writes them)."""
    stats, events, metadata = {}, [], []
    for at, (span, start, end, args) in enumerate(spans, 1):
        found = []
        for key, value in args.items():
            stats.setdefault(key, len(stats) + 1)
            found.append("stats { metadata_id: %d %s }" % (
                stats[key], 'str_value: "%s"' % value
                if isinstance(value, str) else "int64_value: %d" % value))
        events.append("events { metadata_id: %d offset_ps: %d duration_ps: "
                      "%d %s }" % (at, round(start * 10 ** 6),
                                   round((end - start) * 10 ** 6),
                                   " ".join(found)))
        metadata.append('event_metadata { key: %d value { id: %d name: '
                        '"%s" } }' % (at, at, span))
    return """
planes {
  name: "%s"
  lines { name: "python" timestamp_ns: 0
    %s
  }
  %s
  %s
}
""" % (name, "\n    ".join(events), "\n  ".join(metadata), "\n  ".join(
        'stat_metadata { key: %d value { id: %d name: "%s" } }' % (i, i, key)
        for key, i in stats.items()))


def device_plane(ops, module):
    events, metadata = [], []
    for at, (text, path, runs) in enumerate(ops, 1):
        metadata.append(
            'event_metadata { key: %d value { id: %d name: "%s" stats { '
            'metadata_id: 9 str_value: "%s" } } }' % (at, at, text, path))
        events.extend((start, "events { metadata_id: %d offset_ps: %d "
                       "duration_ps: %d }" % (at, start * 10 ** 6,
                                              length * 10 ** 6))
                      for start, length in runs)
    return """
planes {
  name: "/device:TPU:0"
  lines { name: "XLA Ops" timestamp_ns: 0
    %s
  }
  lines { name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 99 offset_ps: %d duration_ps: %d }
  }
  %s
  event_metadata { key: 99 value { id: 99 name: "jit__lambda_(1)" } }
  stat_metadata { key: 9 value { id: 9 name: "tf_op" } }
}
""" % ("\n    ".join(text for _, text in sorted(events)),
       module[0] * 10 ** 6, (module[1] - module[0]) * 10 ** 6,
       "\n  ".join(metadata))


class Run:
    """What a reader is given, as far as these readers look."""

    def __init__(self, trace_dir):
        from benchmark.harness import Lookup

        self.lookup = Lookup()
        self.trace_dir = str(trace_dir) if trace_dir else None
        self.reduced = xplane.load(self.trace_dir) if trace_dir else None
        self.peaks, self.facts = {"some": "peaks"}, {}


def read(name, run):
    return run.lookup.module("layer_metrics", name).read(run)


DEVICE_READERS = ("decoder_idle_ms_per_call", "prefill_device_ms_per_call",
                  "decode_device_step_ms", "decode_unscoped_ms_per_step")


def written(tmp_path, spans=SPANS, ops=OPS):
    from jax.profiler import ProfileData

    tmp_path.mkdir(exist_ok=True)
    (tmp_path / "written.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(
            device_plane(ops, (1000, 1900)) + host_plane(spans)))
    return Run(tmp_path)


def test_the_call_is_read_from_its_span_and_the_idle_time_split(tmp_path,
                                                                capsys):
    run = written(tmp_path)
    trace, spans, calls = decoder_trace.traced(run)
    assert [s.name for s in spans] == [name for name, _, _, _ in SPANS[1:]]
    call, = calls
    assert call.args == SPANS[2][3]
    assert decoder_trace.steps_of(call) == 2
    assert decoder_trace.prefill_applications(call) == 3
    idle = decoder_trace.idle_by_span(trace, spans, 0)
    expected = {xplane.NO_SPAN: 150, "bench/generate (no program span)": 20,
                "decode/call": 40, "decode/prep": 330,
                "decode/dispatch": 190, "decode/fetch": 570}
    assert set(idle) == set(expected)
    for name, us in expected.items():
        assert idle[name] == pytest.approx(us * US, abs=3e-9), name
    # it closes against the reduction the breakdown has
    old = xplane.idle_gaps(trace, 0)
    assert old["bench/generate"] == pytest.approx(1150 * US, abs=3e-9)
    assert sum(s for n, s in idle.items() if n != xplane.NO_SPAN) == \
        pytest.approx(old["bench/generate"], abs=1e-12)
    # and is what cutting every gap at every span's edge gives: the
    # executor's reduction, had it known the prefix
    assert read("decoder_idle_ms_per_call", run) == pytest.approx(1.130)
    printed = capsys.readouterr().out
    assert "decode/fetch 0.570" in printed
    assert "0.940 of it before the call's first program starts" in printed
    assert "bench/generate (no program span) 0.020" in printed
    # self times on the host: the call's own 2030 - 330 - 190 - 1470
    assert "decode/call 0.040" in printed


def test_the_scopes_give_the_prefill_and_the_scan_of_steps(tmp_path, capsys):
    run = written(tmp_path)
    run.facts = {"call_ms": 3.0, "prefill_ms": 1.0, "gen_len": 3}
    part, = decoder_trace.parts(run)
    assert part.prefill == (pytest.approx(1010 * US), pytest.approx(1260 * US))
    # not the operation the compiler moved out of the loop, nor the
    # prefill's two `while`s
    assert part.steps == (pytest.approx(1300 * US), pytest.approx(1800 * US))
    assert part.steps_name == "while.2"
    assert read("prefill_device_ms_per_call", run) == pytest.approx(0.214)
    assert "3 application(s) of 1 position(s) for a prompt of 3, 0.071 ms " \
        "an application; 0.010 ms of the scope's operations ran after" \
        in capsys.readouterr().out
    assert read("decode_device_step_ms", run) == pytest.approx(0.130)
    printed = capsys.readouterr().out
    assert "over 2 steps of %while.2 (0.500 ms)" in printed
    # the host clock's reader of the cell, beside it: (3 - 1) / 2
    assert "decode_step_ms 1.0000 ms" in printed
    assert read("decode_unscoped_ms_per_step", run) == pytest.approx(0.030)
    printed = capsys.readouterr().out
    # a copy states its operand and its result, 2 x 64 bytes, once a step
    assert "copy 0.0200 ms (x1.0, 0.000 MB stated)" in printed
    assert "loop fusion 0.0100 ms (x1.0" in printed
    found = decoder_trace.unscoped(part)
    assert found["copy"] == [pytest.approx(40 * US), 2, 2 * 128]
    assert found["loop fusion"] == [pytest.approx(20 * US), 2,
                                    2 * (4 * 4 + 4 * 16 * 2)]


def test_a_call_without_a_prompt_has_no_prefill_and_one_step_more(tmp_path):
    spans = [s if s[0] != "decode/call" else
             s[:3] + (dict(s[3], mode="greedy", prompt_len=0),)
             for s in SPANS]
    ops = [op for op in OPS if "decode_prefill" not in op[1]]
    run = written(tmp_path, spans, ops)
    assert read("prefill_device_ms_per_call", run) is None
    # max_len steps where no prefill gave the first token
    assert read("decode_device_step_ms", run) == pytest.approx(0.260 / 3)


def test_a_call_loaded_from_before_the_scopes_reads_spans_alone(tmp_path):
    """The compile cache's key leaves `op_name` out: a traced call may run
    a program compiled before the scopes existed.  The span reader still
    reads; the three scope readers give no value."""
    ops = [(text, path.replace("decode_prefill/", "")
            .replace("decode_steps/", ""), runs) for text, path, runs in OPS]
    run = written(tmp_path, ops=ops)
    assert read("decoder_idle_ms_per_call", run) == pytest.approx(1.130)
    for name in DEVICE_READERS[1:]:
        assert read(name, run) is None, name


def test_no_span_no_value(tmp_path):
    """The parent's trace (no `decode/*` span), a CPU rehearsal (no
    peaks), an untraced run: None from every reader, never 0."""
    no_spans = written(tmp_path / "parent", SPANS[:2])
    assert decoder_trace.traced(no_spans) is None
    rehearsal = written(tmp_path / "cpu")
    rehearsal.peaks = None
    untraced = Run(None)
    for run in (no_spans, rehearsal, untraced):
        for name in DEVICE_READERS:
            assert read(name, run) is None, name
    assert read("decoder_prep_ms_per_call", rehearsal) is None


def test_prep_is_read_from_the_programs_counters(tmp_path, capsys,
                                                 monkeypatch):
    run = Run(None)
    monkeypatch.setattr(decoder_trace, "counters", lambda: {})
    assert read("decoder_prep_ms_per_call", run) is None
    monkeypatch.setattr(decoder_trace, "counters", lambda: {
        "decoder_calls_total{mode=greedy-prefill}": 3,
        "decoder_calls_total{mode=greedy}": 1,
        "decoder_programs_total{mode=greedy-prefill}": 2,
        "decoder_tokens_total{kind=generated}": 4096,
        "decoder_state_bytes_total{source=host}": 8e9,
        "decoder_state_bytes_total{source=device}": 4e9,
        "decoder_seconds_total{phase=prep}": 1.2,
        "decoder_seconds_total{phase=dispatch}": 8.0,
        "decoder_seconds_total{phase=fetch}": 20.0})
    assert read("decoder_prep_ms_per_call", run) == pytest.approx(300.0)
    printed = capsys.readouterr().out
    assert "4 calls, 2 programs built" in printed
    assert "2.000 GB of state from the host and 1.000 GB as device arrays, " \
        "10.00 GB/s" in printed
    assert "prep 0.3000, dispatch 2.0000, fetch 5.0000" in printed
    run.peaks = None        # a rehearsal on the CPU
    assert read("decoder_prep_ms_per_call", run) is None


def test_the_counters_are_the_programs_own():
    """What `counters` and `labelled` make of the registry after a call
    of a real decoder, on the CPU."""
    import numpy as np

    import paddle_tpu.fluid as fluid

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        tok = fluid.layers.data(name="tok", shape=[-1], dtype="int64",
                                append_batch_size=False)
        h_in = fluid.layers.data(name="h_in", shape=[-1, 4],
                                 dtype="float32", append_batch_size=False)
        h_out = fluid.layers.fc(
            input=[fluid.layers.embedding(tok, size=[7, 4]), h_in], size=4)
        logits = fluid.layers.fc(input=h_out, size=7)
    scope = fluid.Scope()
    fluid.Executor(fluid.CPUPlace()).run(startup, scope=scope)
    decoder = fluid.ProgramDecoder(
        main.clone(for_test=True), token_name="tok",
        logits_name=logits.name, state_pairs=[("h_in", h_out.name)],
        scope=scope)
    before = decoder_trace.counters()
    decoder.greedy(bos=1, eos=0, max_len=3,
                   init_state={"h_in": np.zeros((2, 4), np.float32)})
    after = decoder_trace.counters()
    calls = decoder_trace.labelled(after, "decoder_calls_total", "mode")
    assert calls["greedy"] == decoder_trace.labelled(
        before, "decoder_calls_total", "mode").get("greedy", 0) + 1
    assert set(decoder_trace.labelled(after, "decoder_seconds_total",
                                      "phase")) == {"prep", "dispatch",
                                                    "fetch"}
    assert decoder_trace.labelled(after, "decoder_state_bytes_total",
                                  "source")["host"] >= 32


# -- recordings from the chip ---------------------------------------------------

def recorded(tmp_path, name, spans=None):
    """A `Run` over a copy of the recording `data/<name>`, with `spans`
    as one more host plane laid beside the recording's own (a file of
    planes after a file of planes is a file of both)."""
    from jax.profiler import ProfileData

    with open(os.path.join(DATA, name), "rb") as f:
        planes = f.read()
    if spans:
        planes += ProfileData.text_proto_to_serialized_xspace(
            host_plane(spans, "/host:decoder"))
    (tmp_path / name).write_bytes(planes)
    return Run(tmp_path)


def test_a_recording_from_before_the_spans_gives_no_value(tmp_path):
    """PR 44's recording of the cell: `bench/window` alone on the host,
    paths without the two scopes."""
    run = recorded(tmp_path, "exaone-turn-32k-ep16-steps.xplane.pb")
    assert run.reduced.devices and run.reduced.spans
    for name in DEVICE_READERS:
        assert read(name, run) is None, name
    # nor with the spans beside it: the scopes are not in its paths
    (tmp_path / "with").mkdir()
    run = recorded(tmp_path / "with", "exaone-turn-32k-ep16-steps.xplane.pb",
                   [("bench/generate", 1, 19600, {}),
                    ("decode/call", 2, 19500, SPANS[2][3])])
    assert read("decoder_idle_ms_per_call", run) is not None
    for name in DEVICE_READERS[1:]:
        assert read(name, run) is None, name


# `data/exaone-turn-32k-ep16-call.xplane.pb` is a recording from the chip
# (TPU v5 lite, exaone-turn-32k-ep16 traced on --seed 2500000129 on an
# empty compile cache, my chip run, PR 50, call 1) cut by
# benchmark/tests/cut_scan_recording.py: of device 0's traced call, step
# 447 of the 895 of the decoding scan (`%while.565`, 8761.474 ms), 1187
# operations in 9488.82 us with their paths as the chip wrote them
# (`jit(<lambda>)/decode_steps/while/body/closed_call/cached_attention/
# ~cached_attention_0.tmp_0/...`; the copies the compiler put into the loop
# carry the loop's own path, `jit(<lambda>)/decode_steps/while:`), and as
# "prefill" what the tool took for one: an iteration of a four-step search
# of the grouped products (0.6 us; since PR 46 the question's prefill is
# one application and no scan), whose paths name no scope.  The tool keeps
# `bench/window` alone of the host, so the call's spans are laid beside the
# recording as the chip's trace had them (`decode/call` around everything,
# its arguments the call's but for `max_len` 2: one step is there).  Of the
# whole scan the run itself printed a step at 9.7846 ms on the device
# (`long_decode_step_ms` 9.8049 on the host's clock) and 1.1982 ms under no
# op instance: copy-done 0.8523, async-done 0.3328, loop fusion 0.0071,
# reduce-window 0.0039, dynamic-update-slice 0.0008.
RECORDED_CALL = {"mode": "greedy-prefill", "call": 5, "batch": 8,
                 "prompt_len": 128, "max_len": 2, "block": 128, "built": 0}


def test_the_readers_on_a_recording_from_the_chip(tmp_path, capsys):
    run = recorded(tmp_path, "exaone-turn-32k-ep16-call.xplane.pb", [
        ("bench/generate", 0.05, 9491.4, {}),
        ("decode/call", 0.1, 9491.3, RECORDED_CALL),
        ("decode/prep", 0.2, 0.5, {"host_bytes": 2176000000,
                                   "device_bytes": 0}),
        ("decode/dispatch", 0.6, 0.9, {}),
        ("decode/fetch", 1.0, 9491.2, {})])
    run.facts = {"long_call_ms": 9088.9, "long_prefill_ms": 327.9,
                 "long_gen_len": 896}
    part, = decoder_trace.parts(run)
    assert part.steps_name == "while.565"
    assert part.steps == (pytest.approx(1.6 * US),
                          pytest.approx(9490.42 * US))
    assert decoder_trace.steps_of(part.call) == 1
    assert read("decode_device_step_ms", run) == pytest.approx(9.483706,
                                                               abs=1e-6)
    printed = capsys.readouterr().out
    assert "over 1 steps of %while.565 (9.489 ms)" in printed
    assert "long_decode_step_ms 9.7888 ms" in printed
    assert read("decode_unscoped_ms_per_step", run) == pytest.approx(
        1.19922, abs=1e-6)
    assert "copy-done 0.8525 ms (x115.0, 1.821 MB stated), async-done " \
        "0.3335 ms (x124.0, 0.000 MB stated), loop fusion 0.0071 ms (x15.0" \
        in capsys.readouterr().out
    found = decoder_trace.unscoped(part)
    # nothing that moves a cache: the step's one update in place states
    # 29 KB, and no plain `copy` more than its 8 token ids
    assert found["dynamic-update-slice"][1:] == [1, 28640]
    assert found["copy"][1:] == [1, 0]
    # the search the tool kept as "prefill" names no scope
    assert part.prefill is None
    assert read("prefill_device_ms_per_call", run) is None
    # the tool keeps no "XLA Modules" line, so every gap between two
    # operations counts as one between programs: the call's 9491.2 us less
    # the step's 9483.706 and the search's 0.586 busy
    assert read("decoder_idle_ms_per_call", run) == pytest.approx(
        0.007007, abs=1e-5)
