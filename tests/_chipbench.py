"""Collect the chip benchmark's CPU tests (``chipbench/tests``) with the
repo's own.  ``load(name)`` imports one of those test modules as that
directory's ``conftest.py`` would have it: that ``conftest`` puts
``chipbench/`` and ``src/`` on ``sys.path``, and the modules import it
by name, which here is ``tests/conftest.py``'s, so it stands in for
that one while they import."""
import importlib
import sys
from pathlib import Path

import pytest

BENCH_TESTS = str(Path(__file__).resolve().parents[1] / "chipbench" / "tests")


def load(name: str) -> dict:
    """The public names of ``chipbench/tests/<name>.py``: its tests and
    fixtures, for a thin module here to take over."""
    pytest.register_assert_rewrite(name)
    ours = sys.modules.pop("conftest", None)
    sys.path.insert(0, BENCH_TESTS)
    try:
        importlib.import_module("conftest")
        mod = importlib.import_module(name)
    finally:
        sys.path.remove(BENCH_TESTS)
        sys.modules.pop("conftest", None)
        if ours is not None:
            sys.modules["conftest"] = ours
    return {k: v for k, v in vars(mod).items() if not k.startswith("__")}
