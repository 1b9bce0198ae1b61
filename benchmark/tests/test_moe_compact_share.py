"""The reader `moe_compact_share` on written traces: the part of the
two row paths' device time that lies under `moe_compact`, 0 and 100 at
the ends, nothing where no operation carries either path's scope (every
program before the paths, every op with one body), and the unchanged
readers of the expert layer's scopes still finding their scope through
the `cond` and the path's scope."""

import pytest

from benchmark.tests.test_ouro_cell import (LOOKUP, Run, _event, _fusion,
                                            _metadata, _read)
from benchmark.tests.test_smallthinker_cell import _trace_dir

FWD = "jit(segment_fn)/moe_experts/~moe_0.tmp_0/"
BWD = "jit(segment_fn)/moe_experts_grad/~moe_0.tmp_0/"
TAKEN, OTHER = "cond/branch_1_fun/", "cond/branch_0_fun/"
# Device time in microseconds, one traced "step":
#   fusion 1   0 ..  4  moe_route, outside the cond (the ordering)
#   fusion 2   4 .. 10  moe_experts/moe_compact, 6 us
#   fusion 3  10 .. 12  moe_combine/moe_compact, 2 us
#   fusion 4  12 .. 20  a projection: nobody's
#   fusion 5  20 .. 24  gradient, moe_experts/moe_all_rows, 4 us
#   fusion 6  24 .. 28  gradient, moe_route/moe_all_rows, 4 us
OPS = [
    (1, 0, 4, _fusion(1), FWD + "moe_route/sort:"),
    (2, 4, 6, _fusion(2), FWD + TAKEN + "moe_experts/moe_compact/mul:"),
    (3, 10, 2, _fusion(3), FWD + TAKEN + "moe_combine/moe_compact/add:"),
    (4, 12, 8, _fusion(4, "kOutput"), "jit(segment_fn)/mul/dot_general:"),
    (5, 20, 4, _fusion(5), BWD + OTHER + "moe_experts/moe_all_rows/mul:"),
    (6, 24, 4, _fusion(6), BWD + OTHER + "moe_route/moe_all_rows/add:"),
]


def _written(ops):
    return """
planes {
  name: "/device:TPU:0"
  lines { name: "XLA Ops" timestamp_ns: 0
    %s
  }
  %s
  stat_metadata { key: 9 value { id: 9 name: "tf_op" } }
}
planes {
  name: "/host:CPU"
  lines { name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 30000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench/window" } }
}
""" % ("\n    ".join(_event(i, s, n) for i, s, n, _, _ in ops),
       "\n  ".join(_metadata(i, text, path) for i, _, _, text, path in ops))


PEAKS = LOOKUP.json("", "peaks")["devices"]["TPU v5 lite"]


def _run(tmp_path, name, ops, steps=1):
    where = tmp_path / name
    where.mkdir()
    return Run(_trace_dir(where, _written(ops)), PEAKS, steps)


def test_the_share_of_both_paths_time_under_the_compact_one(tmp_path, capsys):
    run = _run(tmp_path, "both", OPS)
    assert _read("moe_compact_share", run) == pytest.approx(100 * 8 / 16)
    assert "moe_compact 0.008 ms a step, moe_all_rows 0.008 ms a step" \
        in capsys.readouterr().out
    # two steps in the window: the same share
    assert _read("moe_compact_share", _run(tmp_path, "two", OPS, 2)) \
        == pytest.approx(50.0)


@pytest.mark.parametrize("keep,share", [("moe_compact", 100.0),
                                        ("moe_all_rows", 0.0)])
def test_the_ends(tmp_path, keep, share):
    ops = [op for op in OPS
           if keep in op[4] or "/moe_compact" not in op[4]
           and "/moe_all_rows" not in op[4]]
    assert _read("moe_compact_share", _run(tmp_path, keep, ops)) == share


def test_nothing_where_no_operation_is_under_either_path(tmp_path):
    """An expert op with one body (the parent commit's every program,
    an op that holds all its experts or orders under 32768 rows), an
    untraced run."""
    one_body = [(i, s, n, text, path.replace(TAKEN, "").replace(OTHER, "")
                 .replace("moe_compact/", "").replace("moe_all_rows/", ""))
                for i, s, n, text, path in OPS]
    assert _read("moe_compact_share",
                 _run(tmp_path, "one_body", one_body)) is None
    # an untraced run, and a rehearsal on the CPU (no peaks)
    assert _read("moe_compact_share", Run(None, PEAKS)) is None
    both = _run(tmp_path, "cpu", OPS)
    both.peaks = None
    assert _read("moe_compact_share", both) is None


def test_the_tiny_cell_is_under_32768_rows_and_has_one_body():
    """The fixture's cell orders 128 x 2 rows a layer: no compact path
    is lowered there, so a rehearsal's program carries neither scope."""
    from paddle_tpu.ops.moe import _compact_rows

    config = LOOKUP.json("configs", "smallthinker-tiny")
    tokens = LOOKUP.json("workloads", "smallthinker-tiny-train")["batch"] \
        * config["sequence_length"]
    assert _compact_rows(tokens, config["moe_num_active_primary_experts"],
                         config["moe_num_primary_experts"],
                         config["scored_experts"]) == 0


def test_the_scope_readers_look_through_the_cond(tmp_path, capsys):
    """`moe_ms_per_step` and `moe_route_ms_per_step`, unchanged, find the
    op's three scopes behind `cond/branch_*` and in front of the path's
    scope."""
    run = _run(tmp_path, "scopes", OPS)
    assert _read("moe_ms_per_step", run) == pytest.approx(20e-3)
    printed = capsys.readouterr().out
    assert "moe_experts/moe_experts 0.006 ms" in printed
    assert "moe_experts_grad/moe_route 0.004 ms" in printed
    assert _read("moe_route_ms_per_step", run) == pytest.approx(
        (4 + 2 + 4) * 1e-3)
