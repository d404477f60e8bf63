"""What a test may pin of ``BENCHMARK.json``: membership and relative
order, never a position or an exact list.

A later change adds a configuration, a cell or a metric by appending an
entry and adding files, and edits no file that is there. A test that
holds an entry to be the last one, or a ``workloads`` list to be exactly
today's, breaks on such an append; these three checks do not, and still
catch an entry that went missing, moved ahead of another, or lost a
cell (``test_appendable.py`` holds them to both).
"""

import json
import os


def read(root):
    """The ``BENCHMARK.json`` at the root of a checkout."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _names(listing):
    """The names of a listing of entries (dicts with a ``name``) or of
    names."""
    return [e["name"] if isinstance(e, dict) else e for e in listing]


def entry(listing, name):
    """The one entry of `listing` called `name`."""
    found = [e for e in listing if e["name"] == name]
    assert len(found) == 1, f"{len(found)} entries named {name!r}"
    return found[0]


def stand_in_order(listing, names):
    """`names` stand in `listing` in this order; other entries may come
    before, between and after them."""
    have = _names(listing)
    at = 0
    for name in names:
        assert name in have[at:], (
            f"{name!r} does not stand after {have[at - 1]!r}" if at
            else f"{name!r} is not in the listing")
        at = have.index(name, at) + 1


def listed_for(metric, cells):
    """`metric` is reported in every one of `cells`, by the harness's
    own rule: a metric without ``workloads`` is every cell's. It may
    list more cells."""
    have = metric.get("workloads")
    if have is None:
        return
    missing = [c for c in cells if c not in have]
    assert not missing, f"{metric['name']} does not list {missing}"


def entry_except_workloads(got, want):
    """`got` equals `want` on every key but ``workloads``, which either
    both have or neither, and whose check is :func:`listed_for`."""
    assert ("workloads" in got) == ("workloads" in want), got
    rest = {k: v for k, v in got.items() if k != "workloads"}
    assert rest == {k: v for k, v in want.items() if k != "workloads"}
    listed_for(got, want.get("workloads", []))
