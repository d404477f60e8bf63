"""Names in data files, resolved to files of a benchmark's directory.

A benchmark lives in one directory (``BENCHMARK.json``'s first
``paths`` entry) with a subdirectory per kind of thing: ``traffic/``,
``cells/``, ``layer_metrics/`` (data), ``owners/``, ``datasets/``,
``semantics/``, ``rooflines/`` (one small module each). A name is
looked up in the cell's own benchmark directory first and then in this
one, so a benchmark elsewhere can use what is here beside its own.
"""

from __future__ import annotations

import importlib.util
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))

#: the entry a configuration or mix gets where its file names none
DEFAULTS = {"owners": "inproc_server", "datasets": "pokec_synthetic",
            "semantics": "pokec_graph"}


def find(dirs, sub: str, name: str, ext: str) -> str | None:
    """The first ``<dir>/<sub>/<name><ext>`` that is there."""
    for base in dirs or [HERE]:
        path = os.path.join(base, sub, name + ext)
        if os.path.isfile(path):
            return path
    return None


def load_module(dirs, sub: str, name: str):
    """``<sub>/<name>.py`` as a module, loaded once per file."""
    path = find(dirs, sub, name, ".py")
    if path is None:
        raise LookupError(
            f"no {sub}/{name}.py under {list(dirs or [HERE])}")
    key = "chipbench_seam:" + path
    if key not in sys.modules:
        spec = importlib.util.spec_from_file_location(key, path)
        module = importlib.util.module_from_spec(spec)
        sys.modules[key] = module
        try:
            spec.loader.exec_module(module)
        except BaseException:
            del sys.modules[key]
            raise
    return sys.modules[key]
