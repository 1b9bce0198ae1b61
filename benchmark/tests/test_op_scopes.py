"""From an operation's `op_name` path to an op type and a pass, the read
of the profiler's event metadata from the file itself, and device time by
scope on a written trace."""

import pytest

from benchmark.reduce import op_scopes

US = 1e-6


@pytest.mark.parametrize("path,parts,kind", [
    # what XLA:TPU's profiler writes as `tf_op` (a trailing colon)
    ("jit(segment_fn)/conv2d/conv_general_dilated:",
     ("jit(segment_fn)", "conv2d", "conv_general_dilated"), "conv2d"),
    # a generic gradient: the forward again, under the grad op's scope
    ("jit(segment_fn)/conv2d_grad/transpose(jvp())/conv_general_dilated:",
     ("jit(segment_fn)", "conv2d_grad", "", "conv_general_dilated"),
     "conv2d_grad"),
    # a scope opened under a transformation is wrapped in its name
    ("jit(step)/flash_attention_grad/transpose(flash_attention_grad)/"
     "jvp(flash_attention_bwd)/while/body/closed_call/mul",
     ("jit(step)", "flash_attention_grad", "flash_attention_grad",
      "flash_attention_bwd", "while", "body", "closed_call", "mul"),
     "flash_attention_grad"),
    # nested jits are not scopes
    ("jit(segment_fn)/lookup_table/jit(_take)/gather:",
     ("jit(segment_fn)", "lookup_table", "jit(_take)", "gather"),
     "lookup_table"),
    # the last component is the primitive: `mul` alone is not the op
    ("jit(step)/mul", ("jit(step)", "mul"), None),
    ("jit(segment_fn)/mul/dot_general", ("jit(segment_fn)", "mul",
                                         "dot_general"), "mul"),
    # merged instructions: the first path counts
    ("jit(f)/momentum/sub;jit(f)/sgd/mul", ("jit(f)", "momentum", "sub"),
     "momentum"),
    # an argument's name, a transformation of nothing, no path at all
    ("mut_ins['conv2d_43.w_0']:", ("mut_ins['conv2d_43.w_0']",), None),
    ("jit(segment_fn)/transpose(jvp())/while/body/mul",
     ("jit(segment_fn)", "", "while", "body", "mul"), None),
    ("", ("",), None),
])
def test_components_and_op_type(path, parts, kind):
    assert op_scopes.components(path) == parts
    assert op_scopes.op_type(path) == kind


def test_pass_follows_from_the_type_and_the_optimizers_are_the_programs():
    optimizers = op_scopes.optimizer_op_types()
    assert {"sgd", "momentum", "adam"} <= optimizers
    assert "conv2d" not in optimizers
    assert [op_scopes.pass_of(kind, optimizers) for kind in
            ("conv2d", "conv2d_grad", "momentum", "adam", "while", None)] == \
        ["forward", "backward", "optimizer", "optimizer", "forward",
         "unscoped"]


# A written trace with what the chip's has: the path as the `tf_op` stat of
# the event *metadata* (here once as a string, once as a reference to a
# stat metadata's name, the two forms the profiler uses), which
# `ProfileData` does not show.  Times in microseconds:
#   fusion.1   0 .. 10   conv2d                       forward
#   fusion.2  10 .. 14   conv2d_grad                  backward
#   fusion.3  14 .. 15   momentum                     optimizer
#   copy.4    15 .. 18   no path                      unscoped
#   fusion.5  18 .. 20   flash_attention_grad/.../flash_attention_bwd
#   flash_attention_fwd.6  20 .. 23   under flash_attention_grad
#   flash_attention_fwd.7  23 .. 26   under flash_attention
#   while.8    0 .. 26   a container: left out
WRITTEN = """
planes {
  name: "/device:TPU:0"
  lines { name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 10000000 duration_ps: 4000000 }
    events { metadata_id: 3 offset_ps: 14000000 duration_ps: 1000000 }
    events { metadata_id: 4 offset_ps: 15000000 duration_ps: 3000000 }
    events { metadata_id: 5 offset_ps: 18000000 duration_ps: 2000000 }
    events { metadata_id: 6 offset_ps: 20000000 duration_ps: 3000000 }
    events { metadata_id: 7 offset_ps: 23000000 duration_ps: 3000000 }
    events { metadata_id: 8 offset_ps: 0 duration_ps: 26000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kOutput, calls=%c1"
    stats { metadata_id: 7 str_value: "other" }
    stats { metadata_id: 9 str_value: "jit(segment_fn)/conv2d/conv_general_dilated:" } } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.2 = f32[8]{0} fusion(f32[8]{0} %p), kind=kOutput, calls=%c2"
    stats { metadata_id: 9 ref_value: 11 } } }
  event_metadata { key: 3 value { id: 3 name: "%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, calls=%c3"
    stats { metadata_id: 9 str_value: "jit(segment_fn)/momentum/sub:" } } }
  event_metadata { key: 4 value { id: 4 name: "%copy.4 = f32[8]{0} copy(f32[8]{0} %p)" } }
  event_metadata { key: 5 value { id: 5 name: "%fusion.5 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, calls=%c5"
    stats { metadata_id: 9 str_value: "jit(segment_fn)/flash_attention_grad/transpose(flash_attention_grad)/jvp(flash_attention_bwd)/while/body/closed_call/mul:" } } }
  event_metadata { key: 6 value { id: 6 name: "%flash_attention_fwd.6 = f32[8]{0} custom-call(f32[8]{0} %p), custom_call_target=\\"tpu_custom_call\\""
    stats { metadata_id: 9 str_value: "jit(segment_fn)/flash_attention_grad/transpose(jvp())/flash_attention_fwd:" } } }
  event_metadata { key: 7 value { id: 7 name: "%flash_attention_fwd.7 = f32[8]{0} custom-call(f32[8]{0} %q), custom_call_target=\\"tpu_custom_call\\""
    stats { metadata_id: 9 str_value: "jit(segment_fn)/flash_attention/flash_attention_fwd:" } } }
  event_metadata { key: 8 value { id: 8 name: "%while.8 = (s32[], f32[8]{0}) while(%t), condition=%cond, body=%body"
    stats { metadata_id: 9 str_value: "jit(segment_fn)/while/while:" } } }
  stat_metadata { key: 7 value { id: 7 name: "hlo_category" } }
  stat_metadata { key: 9 value { id: 9 name: "tf_op" } }
  stat_metadata { key: 11 value { id: 11 name: "jit(segment_fn)/conv2d_grad/transpose(jvp())/conv_general_dilated:" } }
}
planes {
  name: "/host:CPU"
  lines { name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 30000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench/window" } }
}
"""


@pytest.fixture(scope="module")
def written(tmp_path_factory):
    from jax.profiler import ProfileData

    path = tmp_path_factory.mktemp("trace") / "written.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(WRITTEN))
    return str(path), ProfileData.from_file(str(path))


def test_paths_are_read_from_the_event_metadata(written):
    path, _ = written
    paths = op_scopes.metadata_stat(path, "/device:TPU:0", "tf_op")
    assert len(paths) == 7          # copy.4 has none
    by_name = {name.split(" = ")[0]: value for name, value in paths.items()}
    assert by_name["%fusion.1"] == \
        "jit(segment_fn)/conv2d/conv_general_dilated:"
    # a reference resolves to the name of the stat metadata it points to
    assert by_name["%fusion.2"] == \
        "jit(segment_fn)/conv2d_grad/transpose(jvp())/conv_general_dilated:"
    assert op_scopes.metadata_stat(path, "/device:TPU:0", "hlo_category") \
        == {next(n for n in paths if n.startswith("%fusion.1 ")): "other"}
    # a stat or a plane that is not there gives nothing, and no error
    assert op_scopes.metadata_stat(path, "/device:TPU:0", "long_name") == {}
    assert op_scopes.metadata_stat(path, "/device:TPU:3", "tf_op") == {}
    assert op_scopes.metadata_stat(path, "/host:CPU", "tf_op") == {}


def test_device_time_by_pass_type_and_scope(written):
    path, profile = written
    paths = op_scopes.metadata_stat(path, "/device:TPU:0", "tf_op")
    found = op_scopes.scoped(profile, paths, 0, (0.0, 30 * US))
    assert len(found.ops) == 7      # the container is left out
    optimizers = op_scopes.optimizer_op_types()
    by_pass = found.seconds(
        lambda p: op_scopes.pass_of(op_scopes.op_type(p), optimizers))
    assert {k: (pytest.approx(v[0]), v[1]) for k, v in by_pass.items()} == {
        "forward": (13 * US, 2), "backward": (9 * US, 3),
        "optimizer": (1 * US, 1), "unscoped": (3 * US, 1)}
    assert sum(v[0] for v in by_pass.values()) == pytest.approx(26 * US)
    assert found.under("flash_attention_bwd") == (pytest.approx(2 * US), 1)
    assert found.under("no_such_scope") == (0.0, 0)
    kernel = found.seconds(
        lambda p: (op_scopes.op_type(p) or "").endswith("_grad"),
        "flash_attention_fwd")
    assert kernel[True][1] == 1 and kernel[False][1] == 1
    # a window that cuts fusion.1 in half cuts its time
    cut = op_scopes.scoped(profile, paths, 0, (5 * US, 30 * US))
    assert cut.seconds(op_scopes.op_type)["conv2d"] == \
        [pytest.approx(5 * US), 1]
    assert found.names_its_ops()


def test_a_program_without_op_scopes_is_told_apart(written):
    """Before the scopes a path goes `jit(segment_fn)/<primitive>`, with
    here and there one of JAX's own scopes that is also an op type
    (`cond` around a platform-dependent kernel): the split would be
    noise, so the readers report none."""
    _, profile = written
    paths = {}
    for plane in profile.planes:
        for line in plane.lines:
            for ev in line.events:
                paths[ev.name] = "jit(segment_fn)/jit(_einsum)/dot_general:"
    kernel = next(n for n in paths if n.startswith("%flash_attention_fwd.6"))
    paths[kernel] = "jit(segment_fn)/cond/branch_0_fun/flash_attention_fwd:"
    found = op_scopes.scoped(profile, paths, 0, (0.0, 30 * US))
    assert found.seconds(op_scopes.op_type)["cond"][1] == 1
    assert not found.names_its_ops()
