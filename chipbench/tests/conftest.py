"""CPU tests of the chip benchmark: ``python -m pytest chipbench/tests``.

The harness runs here on the CPU at a tiny size (``data/``), with the
look for a chip skipped; nothing here gives a device number."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]
os.environ.setdefault("JAX_PLATFORMS", "cpu")


@pytest.fixture
def tiny(monkeypatch, tmp_path):
    """Point the harness at the tiny cells and a scratch state dir."""
    from harness import files, main
    monkeypatch.setattr(files, "BENCH_PATH",
                        os.path.join(HERE, "data", "BENCHMARK.json"))
    monkeypatch.setattr(files, "DATA_DIR", os.path.join(HERE, "data"))
    monkeypatch.setattr(main, "STATE_DIR", str(tmp_path / "state"))
    return files
