"""Fixtures of the benchmark's tests."""
import os
import shutil

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture(scope="session")
def cells_root(tmp_path_factory):
    """A checkout root of its own (its ``BENCHMARK.json`` a copy, its
    ``bench/`` the repository's): what a run caches goes there."""
    root = tmp_path_factory.mktemp("cells")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    (root / "bench").symlink_to(os.path.join(ROOT, "bench"))
    return str(root)
