"""The documents name files that exist.

Every backticked token in ``README.md`` and ``docs/*.md`` that starts
with a directory of this repo must be a path in the tree.  ``:line`` and
``::name`` suffixes and trailing punctuation are stripped; a token with
a glob, a placeholder or a parent reference is skipped; a bare file name
(``router.py``, ``run_report.json``) is not a path and is not checked.
"""

import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOTS = ("ml_trainer_tpu/", "benchmark/", "scripts/", "tests/", "docs/",
         "examples/", "csrc/")
DOCUMENTS = ["README.md"] + sorted(
    os.path.join("docs", name)
    for name in os.listdir(os.path.join(REPO, "docs"))
    if name.endswith(".md")
)
_SKIP = ("*", "..", "<", "{", "$")


def named_paths(text: str) -> list:
    """The repo paths a document's backticked tokens name."""
    paths = []
    for span in re.findall(r"`([^`\n]+)`", text):
        token = (span.split() or [""])[0]
        if not token.startswith(ROOTS) or any(c in token for c in _SKIP):
            continue
        token = token.split("::")[0]
        token = re.sub(r":\d+(-\d+)?$", "", token.rstrip(".,;:)"))
        paths.append(token)
    return paths


@pytest.mark.parametrize("document", DOCUMENTS)
def test_every_path_a_document_names_exists(document):
    with open(os.path.join(REPO, document), encoding="utf-8") as fp:
        paths = named_paths(fp.read())
    missing = sorted({
        p for p in paths if not os.path.exists(os.path.join(REPO, p))
    })
    assert not missing, f"{document} names files not in the tree: {missing}"


def test_named_paths_rule():
    text = (
        "`scripts/perf_diff.py old new`, `tests/test_x.py::test_y`, "
        "`ml_trainer_tpu/a.py:12-30`. `docs/*_old.json` `router.py` "
        "`benchmark/run.py --workload <cell>` (`docs/serving.md`)."
    )
    assert named_paths(text) == [
        "scripts/perf_diff.py", "tests/test_x.py", "ml_trainer_tpu/a.py",
        "benchmark/run.py", "docs/serving.md",
    ]
