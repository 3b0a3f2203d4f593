"""The chip benchmark's ``chipbench/tests/test_cells.py``, collected with
the repo's tests."""
from _chipbench import load

globals().update(load("test_cells"))
