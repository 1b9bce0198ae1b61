"""The process flags (`paddle_tpu/utils/flags.py`): each one reads its
`FLAGS_<name>` environment variable, each one is read by code, and a
`FLAGS_*` variable that no flag answers to is named, not dropped."""

import logging
import os
import re

import pytest

from paddle_tpu.utils import flags

PACKAGE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "paddle_tpu")

FLAGS = sorted(flags.all_flags())

# flags this tree had and deleted, and a typo of one it has
UNKNOWN = ["compile_passes", "fuse_optimizer", "fuse_optimizer_max_numel",
           "do_memory_benchmark", "use_debug_nans", "chek_nan_inf"]


def _another_value(default):
    """(what the environment says, what the flag then reads) for a
    value that is not the default."""
    if isinstance(default, bool):
        return ("0", False) if default else ("1", True)
    if isinstance(default, int):
        return str(default + 3), default + 3
    if isinstance(default, float):
        return "1.5", 1.5
    return "off", "off"


@pytest.mark.parametrize("flag", FLAGS)
def test_flag_reads_its_environment_variable(flag, monkeypatch):
    default = flags._FLAGS[flag]["default"]
    before = flags.get_flag(flag)
    said, reads = _another_value(default)
    monkeypatch.setenv("FLAGS_" + flag, said)
    try:
        flags.parse_flags_from_env()
        assert flags.get_flag(flag) == reads != default
        assert type(flags.get_flag(flag)) is type(default)
    finally:
        flags.set_flag(flag, before)


@pytest.mark.parametrize("flag", FLAGS)
def test_flag_has_a_reader(flag):
    """A flag nothing reads sets nothing: some module other than the
    registry itself calls `get_flag("<flag>")`."""
    call = re.compile(r"get_flag\(\s*[\"']%s[\"']\s*\)" % re.escape(flag))
    readers = []
    for root, dirs, names in os.walk(PACKAGE):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for name in names:
            path = os.path.join(root, name)
            if not name.endswith(".py") \
                    or os.path.samefile(path, flags.__file__):
                continue
            with open(path) as f:
                if call.search(f.read()):
                    readers.append(os.path.relpath(path, PACKAGE))
    assert readers, "no module reads FLAGS_%s" % flag


@pytest.mark.parametrize("name", UNKNOWN)
def test_unknown_flags_variable_is_named(name, monkeypatch, caplog):
    var = "FLAGS_" + name
    assert name not in flags.all_flags()
    before = flags.all_flags()
    flags._unknown_told.discard(var)
    monkeypatch.setenv(var, "1")
    with caplog.at_level(logging.WARNING, logger="paddle_tpu"):
        flags.parse_flags_from_env()
        flags.parse_flags_from_env()    # once a process
    told = [r for r in caplog.records if var in r.getMessage()]
    assert len(told) == 1, caplog.records
    assert told[0].levelno == logging.WARNING
    assert told[0].name == "paddle_tpu"
    assert flags.all_flags() == before
