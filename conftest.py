"""Toy stand-ins for benchmark cells added since ``benchmark/testdata/
toys.json`` was written. ``benchmark/conftest.py`` adds that file's rows to
every collected test module with a ``TOY`` table; a PR that adds a cell may
add files under ``benchmark/`` but edit none, so a later cell brings its row
as a file of its own, ``benchmark/testdata/toys.d/<cell>.json`` (the same
``{cell: [toy cell, toy config, toy mix]}``), and this hook, which has to sit
in a ``conftest.py`` above ``benchmark/tests``, adds those. It imports nothing
heavy and touches no module without such a table (none under ``tests/``)."""
import glob
import json
import os

TOYS_D = os.path.join(os.path.dirname(os.path.abspath(__file__)), "benchmark",
                      "testdata", "toys.d")


def pytest_collection_modifyitems(session, config, items):
    toys = {}
    for path in sorted(glob.glob(os.path.join(TOYS_D, "*.json"))):
        with open(path) as f:
            toys.update({c: tuple(t) for c, t in json.load(f).items()})
    for module in {item.module for item in items if hasattr(item, "module")}:
        table = getattr(module, "TOY", None)
        if isinstance(table, dict):
            for cell, toy in toys.items():
                table.setdefault(cell, toy)
