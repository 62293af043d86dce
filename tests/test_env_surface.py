"""The `MADSIM_TPU_*` surface: what the code reads is what README.md
documents, name for name.

ROADMAP's Design aim is a small set of variables a user can get right.
A name the code reads and the README leaves out is a switch nobody can
find; a name the README documents and nothing reads is a switch that
does nothing. Either fails its own case here.
"""

import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = re.compile(r"MADSIM_TPU_[A-Z0-9_]*[A-Z0-9]")


def _names(paths) -> set:
    found = set()
    for path in paths:
        with open(path, encoding="utf-8") as f:
            found.update(NAME.findall(f.read()))
    return found


CODE = _names(
    glob.glob(os.path.join(REPO, "madsim_tpu", "**", "*.py"), recursive=True)
    + [os.path.join(REPO, "chip_smoke.py"),
       os.path.join(REPO, "__graft_entry__.py")]
)
DOCS = _names([os.path.join(REPO, "README.md")])


@pytest.mark.parametrize("name", sorted(CODE | DOCS))
def test_env_name_is_read_and_documented(name):
    assert name in CODE, f"README.md documents {name}; no code reads it"
    assert name in DOCS, f"the code reads {name}; README.md has no row for it"
