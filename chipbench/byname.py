"""Code a cell brings as a file, found by its name in a data file: an
architecture (``archs/``), a kind of arrivals (``arrivals/``) or a
metric's reader (``metrics/``)."""
from __future__ import annotations

import importlib.util
from pathlib import Path

_LOADED: dict = {}


def load_module(folder: Path, name: str, what: str):
    """The module ``<folder>/<name>.py``, loaded once; a missing file is a
    ``ValueError`` that names ``what`` was looked for and where."""
    path = Path(folder) / f"{name}.py"
    if path not in _LOADED:
        if not path.is_file():
            raise ValueError(f"no module for {what} {name!r} ({path})")
        spec = importlib.util.spec_from_file_location(
            f"{path.parent.name}_{name}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _LOADED[path] = mod
    return _LOADED[path]
