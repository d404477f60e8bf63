"""The repo's documents and its dev gate name only what exists.

A document that tells a reader to open a file the repo no longer has,
a gate stage whose module cannot be imported and an envelope nothing
enforces all fail silently; these tests make them fail loudly.
"""

import importlib.util
import json
import pathlib
import re

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent

#: envelope of BASELINE.json -> the checker under tools/ that reads it
ENVELOPE_READERS = {"memory": "tools/mgmem/check.py"}

_FENCE = re.compile(r"```.*?```", re.S)
_TOKEN = re.compile(r"`([^`]+)`")
_PATH = re.compile(
    r"^(benchmarks|tools|tests|docs|memgraph_tpu|native)/[\w./-]*\.[a-z]+$")
_RECORD = re.compile(r"^[A-Z]{2}\w*\.(json|md|jsonl)$")
_PYTHON = re.compile(r"\bpython3?\s+(-m\s+)?([\w./]+)")
_SKIP = re.compile(r"[*<>{}…]|^/|^tests/mgbench/")
_LINE_REF = re.compile(r":\d+(-\d+)?$")


def _module_path(module):
    """A `python -m` target as a path, if it is one of the repo's."""
    parts = module.split(".")
    if not (REPO / parts[0]).is_dir():
        return None                     # pytest, pip: not ours to hold
    path = REPO.joinpath(*parts)
    return path if path.is_dir() else path.with_suffix(".py")


def _named_files(text):
    """(name as written, candidate paths) of every file the text names."""
    for dash_m, target in _PYTHON.findall(text):
        if dash_m:
            path = _module_path(target)
            if path is not None:
                yield target, [path]
        elif target.endswith(".py"):
            yield target, [REPO / target]
    for token in _TOKEN.findall(_FENCE.sub("", text)):
        for word in token.split():
            word = _LINE_REF.sub("", word.strip("(),;:"))
            if _SKIP.search(word):
                continue
            if _PATH.match(word):
                yield word, [REPO / word]
            elif _RECORD.match(word):
                yield word, [REPO / word,
                             REPO / "benchmarks" / "chipbench" / word]


def _document(name):
    text = (REPO / name).read_text(encoding="utf-8")
    if name == "PERF.md":
        # §6 and §7 are history: they go on naming what was removed
        text = text[:text.index("\n## 6.")]
    return text


@pytest.mark.parametrize("name", ["README.md", "docs/architecture.md",
                                  "BASELINE.md", "PERF.md",
                                  "tools/gate.sh"])
def test_documents_name_only_files_that_exist(name):
    named = list(_named_files(_document(name)))
    assert named, f"{name}: the rule found no file name to check"
    missing = sorted({written for written, paths in named
                      if not any(p.exists() for p in paths)})
    assert not missing, f"{name} names files the repo lacks: {missing}"


def test_gate_stages_import():
    gate = (REPO / "tools" / "gate.sh").read_text(encoding="utf-8")
    modules = {target for dash_m, target in _PYTHON.findall(gate)
               if dash_m}
    assert len(modules) >= 10, modules
    lost = sorted(m for m in modules
                  if importlib.util.find_spec(m) is None)
    assert not lost, f"tools/gate.sh runs modules that do not import: {lost}"


def test_baseline_envelopes_have_a_reader():
    with open(REPO / "BASELINE.json", encoding="utf-8") as f:
        envelopes = json.load(f)["envelopes"]
    assert set(envelopes) == set(ENVELOPE_READERS)
    for key, reader in ENVELOPE_READERS.items():
        assert f'"{key}"' in (REPO / reader).read_text(encoding="utf-8"), \
            f"{reader} no longer reads envelopes.{key}"
