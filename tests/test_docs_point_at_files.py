"""The documents that tell a reader what to run name files that exist.

One case a document: every `python[3] <script>` it shows and every
back-quoted path ending .py, .json or .md in it is in the checkout (a
document under docs/ may name a path relative to itself or to the
package, as `parallel/train.py`). A command that no longer exists sends
a new owner to measure with the wrong tool."""

import os
import re

import pytest

from conftest import REPO_ROOT

DOCUMENTS = [
    "README.md", "examples/README.md", "docs/DESIGN.md", "docs/TRACING.md",
    "docs/ZERO.md", "docs/COMPRESSION.md", "docs/TRANSPORT.md",
    "docs/AUTOTUNE.md", "docs/SERVE.md", "docs/ELASTIC.md",
    "docs/METRICS.md", "docs/GROUPS.md",
]

# The name every document gives the reader's own training script.
READERS_OWN = {"train.py"}

# A path may carry a `:line` or `::test` suffix inside the quotes.
_QUOTED = re.compile(r"`([^`\s:]+\.(?:py|json|md))(?::[^`\s]*)?`")
_COMMAND = re.compile(r"\bpython3?\s+(?:-[A-Za-z]\s+)*([\w./-]+\.py)\b")


def named_paths(text):
    names = set(_QUOTED.findall(text)) | set(_COMMAND.findall(text))
    # A placeholder or a pattern (`ckpt-<step>/manifest.json`, `shard-*.json`)
    # is not a path; an absolute or home path is outside the checkout.
    return sorted(n for n in names
                  if not re.search(r"[<>*{}$]", n)
                  and not n.startswith(("/", "~"))
                  and os.path.basename(n) not in READERS_OWN)


@pytest.mark.parametrize("document", DOCUMENTS)
def test_document_names_files_that_exist(document):
    path = os.path.join(REPO_ROOT, document)
    text = open(path, encoding="utf-8").read()
    roots = [REPO_ROOT, os.path.dirname(path),
             os.path.join(REPO_ROOT, "horovod_tpu")]
    names = named_paths(text)
    assert names, "%s names no file: the patterns have gone blind" % document
    missing = [n for n in names
               if not any(os.path.exists(os.path.join(r, n)) for r in roots)]
    assert not missing, "%s names files that are not in the checkout: %s" % (
        document, missing)


def test_the_patterns_see_a_dead_script():
    text = ("Run `old_harness.py`, or `python3 tools/gone.py --flag`, see "
            "`docs/DESIGN.md:12` and `ckpt-<step>/manifest.json`; "
            "`python train.py` is yours.")
    assert named_paths(text) == ["docs/DESIGN.md", "old_harness.py",
                                 "tools/gone.py"]
