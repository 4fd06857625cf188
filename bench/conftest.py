"""Tiny sizes of the configurations and traffic that came after the
``TINY`` tables of ``bench/tests/conftest.py``. An autouse fixture puts
them in those tables for each bench test and takes them out after it;
pytest runs autouse fixtures before the ones a test asks for, so
``tiny_bench`` cuts these cells to a thousand particles too. Size keys
only."""

import pytest

from bench.tests import conftest as tiny

TINY = {"kg_melt": {"sites_per_axis": 10, "atoms": 1000}}
TINY_TRAFFIC = {"force_bonded": {"keep_one_in": 2}}


@pytest.fixture(autouse=True)
def later_tiny_sizes(monkeypatch):
    for table, entries in ((tiny.TINY, TINY),
                           (tiny.TINY_TRAFFIC, TINY_TRAFFIC)):
        for name, changes in entries.items():
            monkeypatch.setitem(table, name, changes)
