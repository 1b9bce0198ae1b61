"""The documents name only what the tree has: every `paddle_tpu/…`,
`scripts/…`, `tests/…` or `docs/…` path, every `paddle_tpu.a.b` module
(`python -m paddle_tpu.tools.x` among them) and every `FLAGS_x` that
`README.md`, `PARITY.md` or a file under `docs/` names exists.  A
document that sends a reader to a deleted file fails here; correct the
document, not the rule."""

import importlib
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCUMENTS = ["README.md", "PARITY.md"] + sorted(
    "docs/" + name for name in os.listdir(os.path.join(REPO, "docs"))
    if name.endswith(".md"))

# a path of this tree: not the tail of a longer one (the reference's
# `python/paddle/v2/fluid/tests/book/…`), up to what cannot be a name
_PATH = re.compile(
    r"(?<![\w/.-])((?:paddle_tpu|scripts|tests|docs)/[\w./-]+)")
_MODULE = re.compile(r"(?<![\w/.-])(paddle_tpu(?:\.[A-Za-z_]\w*)+)")
_FLAG = re.compile(r"\bFLAGS_([a-z]\w*)")


def _missing_paths(text):
    for path in sorted(set(_PATH.findall(text))):
        # `tests/test_x.py::case`, `fluid/executor.py:849`, a closing
        # full stop
        path = path.split("::")[0].rstrip("./-")
        path = re.sub(r":\d+$", "", path)
        if not os.path.exists(os.path.join(REPO, path)):
            yield path


def _resolves(dotted):
    """`dotted` is a module, or a name some module on its way holds."""
    parts = dotted.split(".")
    for cut in range(len(parts), 0, -1):
        try:
            found = importlib.import_module(".".join(parts[:cut]))
        except ImportError:
            continue
        try:
            for name in parts[cut:]:
                found = getattr(found, name)
        except AttributeError:
            return False
        return True
    return False


def _missing_modules(text):
    for dotted in sorted(set(_MODULE.findall(text))):
        if not _resolves(dotted):
            yield dotted


def _missing_flags(text):
    from paddle_tpu.utils import flags

    known = set(flags.all_flags())
    for name in sorted(set(_FLAG.findall(text))):
        if name not in known:
            yield "FLAGS_" + name


@pytest.mark.parametrize("document", DOCUMENTS)
def test_doc_names_only_what_exists(document):
    with open(os.path.join(REPO, document)) as f:
        text = f.read()
    missing = (list(_missing_paths(text)) + list(_missing_modules(text))
               + list(_missing_flags(text)))
    assert not missing, "%s names what the tree does not have: %s" \
        % (document, missing)
