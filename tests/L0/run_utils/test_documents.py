"""The documents name only entry points that exist: every ``python
<script>.py`` resolves to a file of the checkout and every
``./run_tests.sh <tier>`` to an arm of that script's ``case``. A script
deleted without a sweep of the words about it fails here."""

import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
DOCUMENTS = ["README.md", "run_tests.sh"] + sorted(
    os.path.relpath(p, REPO)
    for p in glob.glob(os.path.join(REPO, "docs", "source", "*.rst")))

SCRIPT = re.compile(r"\bpython3?\s+([\w./-]+\.py)\b")
TIER = re.compile(r"run_tests\.sh\s+([A-Za-z]\w*)")


def _tiers():
    """The arms of ``run_tests.sh``'s ``case``."""
    text = open(os.path.join(REPO, "run_tests.sh")).read()
    body = text[text.index('case "$tier" in'):text.index("esac")]
    return set(re.findall(r"^\s*(\w+)\)", body, re.M))


def test_the_readme_is_among_the_documents_and_names_entry_points():
    text = open(os.path.join(REPO, "README.md")).read()
    assert {"benchmark/run.py", "chip_smoke.py"} <= set(SCRIPT.findall(text))
    assert {"L0", "L1", "all", "quick", "chaos", "gate", "lint"} <= _tiers()


@pytest.mark.parametrize("document", DOCUMENTS)
def test_document_names_only_entry_points_that_exist(document):
    text = open(os.path.join(REPO, document)).read()
    missing = [s for s in SCRIPT.findall(text)
               if not os.path.isfile(os.path.join(REPO, s))]
    assert not missing, f"{document} runs scripts that are not there"
    unknown = sorted(set(TIER.findall(text)) - _tiers())
    assert not unknown, f"{document} names tiers run_tests.sh lacks"
