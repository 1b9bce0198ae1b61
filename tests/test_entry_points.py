"""Every entry point the tree names starts: each `console_scripts`
target of `setup.py` and each other module under `paddle_tpu/tools/`
resolves by `importlib`, and its `main(["--help"])` prints a usage and
exits 0.  A script named after its module went, or a module whose
import broke, fails here and not at a user's first call."""

import importlib
import os
import re
from unittest import mock

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _console_scripts():
    """{script name: "module:function"} as `setup.py` spells them,
    read from its text: importing it would run `setup()`."""
    with open(os.path.join(REPO, "setup.py")) as f:
        text = f.read()
    block = text[text.index('"console_scripts"'):]
    block = block[:block.index("]")]
    return dict(re.findall(r'"([\w-]+)=([\w.]+:\w+)"', block))


def _entry_points():
    scripts = _console_scripts()
    named = {target.split(":")[0] for target in scripts.values()}
    tools = os.path.join(REPO, "paddle_tpu", "tools")
    others = {}
    for name in sorted(os.listdir(tools)):
        module = "paddle_tpu.tools." + name[:-3]
        if name.endswith(".py") and name != "__init__.py" \
                and module not in named:
            others[name[:-3]] = module + ":main"
    return {**scripts, **others}


ENTRY_POINTS = _entry_points()


@pytest.mark.parametrize("name", sorted(ENTRY_POINTS))
def test_entry_point_prints_help(name, capsys):
    module, function = ENTRY_POINTS[name].split(":")
    main = getattr(importlib.import_module(module), function)
    # some mains pin JAX_PLATFORMS or XLA_FLAGS before they parse
    with mock.patch.dict(os.environ), pytest.raises(SystemExit) as left:
        main(["--help"])
    assert left.value.code == 0
    assert "usage" in capsys.readouterr().out.lower()
