"""CPU rehearsals of the benchmark at tiny sizes: four virtual devices,
so the 4-chip cell's slab runs here too. No chip, no timing."""

import json
import os
import shutil
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")

import pytest  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

#: Each cell's grid in the rehearsals: divisible by the four devices.
TINY = [16, 16, 16]


@pytest.fixture
def tiny_root(tmp_path):
    """A copy of the benchmark (``BENCHMARK.json`` and ``perf/``) whose
    configurations run at ``TINY``; everything else as committed."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "perf", tmp_path / "perf",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    for f in (tmp_path / "perf" / "configs").glob("*.json"):
        cfg = json.loads(f.read_text())
        cfg["shape"] = TINY
        f.write_text(json.dumps(cfg))
    return tmp_path
