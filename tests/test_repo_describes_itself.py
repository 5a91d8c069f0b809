"""The repository names one benchmark, and its documents name files that exist.

`BENCHMARK.json` declares what is measured (`benchmark/run.py`, the cells
under `benchmark/cells/`); nothing else at the root may pass for a
benchmark or its record. A document that names a file is held to the
tree: a deletion that leaves a sentence behind fails here.
"""

from __future__ import annotations

import json
import os
import re
import subprocess
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]

DOCUMENTS = [
    "README.md",
    *sorted(f"docs/{p.name}" for p in (REPO / "docs").glob("*.md")),
    ".claude/skills/verify/SKILL.md",
]

# What PR 29 deleted: a second benchmark, its CI gate and the CPU rounds it
# wrote. None of them comes back under its old name.
FORBIDDEN = ["bench.py", "scripts/bench_check.py", "BENCH_r*.json", "MULTICHIP_r*.json"]

SUFFIXES = (".py", ".json", ".toml", ".yml", ".yaml", ".md", ".sh")
PATTERN_MARKS = ("*", "<", "$", "{")

# Tokens that name no file of this tree, each with its reason.
NOT_OF_THIS_TREE = {
    # paths of the REFERENCE repository, which the documents compare against
    ".github/docs/getting-started.md",
    ".github/docs/step-by-step-setup.md",
    "app/main.py",
    # files a command writes at run time
    "manifest.json",  # a bundle's manifest (`bundle/bundle.py`)
    "latest.json",  # a checkpoint directory's pointer (`train/checkpoint.py`)
    "index.json",  # a registered model's versions (`bundle/registry.py`)
    "plan.json",  # the autotuner's bucket plan (`autotune/apply.py`)
}


@pytest.fixture(scope="module")
def tracked_base_names() -> set[str]:
    """Base names of the files git would commit (ignored scratch copies of
    other commits do not count); in a checkout without `.git`, of every
    file."""
    if (REPO / ".git").exists():
        out = subprocess.run(
            ["git", "ls-files", "--cached", "--others", "--exclude-standard"],
            cwd=REPO, capture_output=True, text=True, check=True,
        ).stdout
        return {Path(f).name for f in out.splitlines() if (REPO / f).exists()}
    return {name for _root, _dirs, names in os.walk(REPO) for name in names}


def _file_tokens(text: str) -> list[str]:
    """Words inside back-quoted spans that end in a file suffix: a pytest
    id's `::case`, a `:line` reference, a `key=` prefix, curl's `@` and
    trailing punctuation are taken off first; patterns and URLs are left
    out."""
    tokens = []
    for span in re.findall(r"`([^`\n]+)`", text):
        for word in span.split():
            word = word.split("::")[0].rsplit("=", 1)[-1]
            word = re.sub(r":\d+(-\d+)?(,\d+(-\d+)?)*$", "", word).rstrip(".,;:)")
            word = word.lstrip("(@")
            if (
                word.endswith(SUFFIXES)
                and "://" not in word
                and not any(m in word for m in PATTERN_MARKS)
            ):
                tokens.append(word)
    return tokens


def test_the_repo_has_one_benchmark():
    declared = json.loads((REPO / "BENCHMARK.json").read_text())
    script = next(a for a in declared["command"] if a.endswith(".py"))
    assert (REPO / script).is_file(), script
    for config in declared["configs"]:
        assert (REPO / config["file"]).is_file(), config["file"]
    for cell in declared["workloads"]:
        cell_file = REPO / "benchmark" / "cells" / f"{cell['name']}.json"
        assert cell_file.is_file(), cell_file
    leftovers = [str(p.relative_to(REPO)) for g in FORBIDDEN for p in REPO.glob(g)]
    assert leftovers == [], f"a second benchmark or its records: {leftovers}"


@pytest.mark.parametrize("document", DOCUMENTS)
def test_a_document_names_only_files_that_exist(document, tracked_base_names):
    path = REPO / document
    roots = (REPO, REPO / "mlops_tpu", path.parent)
    missing = [
        token
        for token in _file_tokens(path.read_text(encoding="utf-8"))
        if token not in NOT_OF_THIS_TREE
        and not any((root / token).exists() for root in roots)
        and not ("/" not in token and token in tracked_base_names)
    ]
    assert missing == [], f"{document} names files that do not exist: {missing}"
