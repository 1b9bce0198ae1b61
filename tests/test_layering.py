"""The package graph of `paddle_tpu/` points one way.

Every package directory and top-level module has one place in `ORDER`,
bottom first.  A module may import what stands below its package; an
import that points up must be in `UP_EDGES`, by the module that makes
it and the package it reaches, with the `ROADMAP.md` Design item that
owns the debt.  The list can only shrink: a case fails on an up-edge
that is not listed, and on a listed edge the tree no longer has.

Read from the source with `ast`, imports inside functions too, so
nothing is imported to be judged."""

import ast
import functools
import os

import pytest

PACKAGE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "paddle_tpu")

# bottom first: what a line names may import what stands before it
ORDER = [
    "core", "native", "utils",          # descs, the native runtime, flags
    "obs",                              # spans, counters, the registry
    "kernels", "ops",
    "resilience", "reader", "dataset",  # faults and retries; input
    "fluid", "jit",                     # Programs and how they run
    "analysis",                         # checks over a Program
    "parallel", "models", "spmd", "distributed", "serving",
    "v2", "trainer_config_helpers",     # the source paper's API
    "capi_impl",
    "__init__",                         # the front door: the public names
    "tools",                            # entry points, which may use it
]

# (module that imports, package it reaches up to): the Design item of
# ROADMAP.md that owns the edge
UP_EDGES = {
    # obs/: the substrate the hot path imports (trace, registry,
    # telemetry, context) and the reporters that read the framework
    ("obs/comm.py", "analysis"): "Design 16(a)",
    ("obs/comm.py", "parallel"): "Design 16(a)",
    ("obs/comm.py", "spmd"): "Design 16(a)",
    ("obs/health.py", "fluid"): "Design 16(a)",
    ("obs/load.py", "fluid"): "Design 16(a)",
    ("obs/load.py", "serving"): "Design 16(a)",
    ("obs/mem.py", "analysis"): "Design 16(a)",
    ("obs/mem.py", "fluid"): "Design 16(a)",
    # resilience/: faults and retries below fluid, the supervisors
    # above the trainers they restart
    ("resilience/elastic.py", "distributed"): "Design 16(b)",
    ("resilience/elastic.py", "parallel"): "Design 16(b)",
    ("resilience/elastic.py", "spmd"): "Design 16(b)",
    ("resilience/supervisor.py", "fluid"): "Design 16(b)",
    # utils/: two Program tools filed with the flags
    ("utils/merge_model.py", "fluid"): "Design 16(c)",
    ("utils/model_diagram.py", "ops"): "Design 16(c)",
    # ops/: sub-block ops that run an Executor, ring attention's mesh
    ("ops/control_flow.py", "fluid"): "Design 16(d)",
    ("ops/sequence.py", "fluid"): "Design 16(d)",
    ("ops/attention.py", "parallel"): "Design 16(d)",
    # fluid/: the executor's verify gate and donation plan; the
    # decoder that builds a model
    ("fluid/executor.py", "analysis"): "Design 16(e), with Design 4",
    ("fluid/io.py", "analysis"): "Design 16(e)",
    ("fluid/memory_optimization_transpiler.py", "analysis"):
        "Design 16(e)",
    ("fluid/fast_decode.py", "jit"): "Design 16(e), with Design 11",
    ("fluid/fast_decode.py", "models"): "Design 16(e), with Design 11",
    # analysis/: the sharding analyzer reads the mesh helpers
    ("analysis/shard.py", "parallel"): "Design 16(f)",
}


def _unit(parts):
    """The package or top-level module a dotted path under
    `paddle_tpu` belongs to."""
    return parts[0] if parts else "__init__"


def _targets(node, package):
    """The dotted paths under `paddle_tpu` that one import statement
    names, each as a list of parts; `package` is the importing
    module's own package, for relative imports."""
    if isinstance(node, ast.Import):
        for alias in node.names:
            parts = alias.name.split(".")
            if parts[0] == "paddle_tpu":
                yield parts[1:]
        return
    if node.level == 0:
        parts = (node.module or "").split(".")
        if parts[0] != "paddle_tpu":
            return
        base = parts[1:]
    else:
        up = node.level - 1
        if up > len(package):
            return
        base = list(package[:len(package) - up])
        if node.module:
            base += node.module.split(".")
    if base:
        yield base
    else:
        # `from . import x` at the top of the package
        for alias in node.names:
            yield [alias.name]


@functools.lru_cache(maxsize=None)
def _graph():
    """{(importing module, package reached)} for every import in the
    tree that leaves its own package, and the set of units found."""
    edges, units = set(), set()
    for root, dirs, names in os.walk(PACKAGE):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for name in names:
            if not name.endswith(".py"):
                continue
            path = os.path.join(root, name)
            rel = os.path.relpath(path, PACKAGE)
            parts = rel[:-3].split(os.sep)
            package = parts[:-1]
            module = package if parts[-1] == "__init__" else parts
            source = _unit(module)
            units.add(source)
            with open(path) as f:
                tree = ast.parse(f.read(), filename=path)
            for node in ast.walk(tree):
                if not isinstance(node, (ast.Import, ast.ImportFrom)):
                    continue
                for target in _targets(node, package):
                    if _unit(target) != source:
                        edges.add((rel.replace(os.sep, "/"),
                                   _unit(target)))
    return edges, units


@pytest.mark.parametrize("package", ORDER)
def test_package_imports_point_down(package):
    edges, units = _graph()
    assert units == set(ORDER), \
        "give these a place in ORDER (or take them out): %s" \
        % sorted(units ^ set(ORDER))
    rank = {unit: i for i, unit in enumerate(ORDER)}

    def source_of(module):
        return module.split("/")[0].removesuffix(".py")

    up = {(module, target) for module, target in edges
          if source_of(module) == package
          and rank[target] > rank[package]}
    listed = {edge for edge in UP_EDGES if source_of(edge[0]) == package}
    assert not up - listed, \
        "%s imports upward and the edge is not listed: %s" \
        % (package, sorted(up - listed))
    assert not listed - up, \
        "listed up-edges that are gone, take them off the list: %s" \
        % sorted(listed - up)
