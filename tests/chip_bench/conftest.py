"""Shared by the chip benchmark's tests: where the benchmark lives and how
its modules are reached (it is not a package of the program; ``run.py``
puts its own directory on ``sys.path`` when loaded)."""
import importlib.util
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = ROOT / "benchmark" / "chip"


@pytest.fixture(scope="session")
def chip_run():
    """``benchmark/chip/run.py`` as a module (``chip_run.main(argv)``)."""
    if "chip_bench_run" not in sys.modules:
        spec = importlib.util.spec_from_file_location("chip_bench_run",
                                                      BENCH / "run.py")
        module = importlib.util.module_from_spec(spec)
        sys.modules["chip_bench_run"] = module
        spec.loader.exec_module(module)
    return sys.modules["chip_bench_run"]


@pytest.fixture(scope="session")
def bench_catalog(chip_run):
    return chip_run.catalog.Catalog(ROOT)
