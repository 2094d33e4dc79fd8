"""The benchmark's own tests run on the CPU, at sizes a test run holds:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q
"""

import json
import os
import shutil
import sys
from pathlib import Path

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

# traffic cut to a test's size; shapes are the configurations' own
SMALL = {
    "crashed-10k": {"ops": 1000, "pool": 2, "corrupt_every": 2},
    "plain-10k": {"ops": 400, "pool": 4, "corrupt_every": 4},
    "keyed-50k": {"keys": 20, "corrupt_key_share": 0.1},
}


@pytest.fixture
def small_root(tmp_path):
    """A checkout holding BENCHMARK.json and a copy of benchmark/ with
    every traffic mix cut to SMALL."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns(".jax_cache", ".trace",
                                                  "__pycache__", "tests"))
    for name, upd in SMALL.items():
        p = tmp_path / "benchmark" / "traffic" / f"{name}.json"
        d = json.loads(p.read_text())
        d.update(upd)
        p.write_text(json.dumps(d))
    return tmp_path


@pytest.fixture
def any_device():
    """A chip check that takes the CPU, for tests that drive a run."""
    import jax

    return lambda n: jax.devices()
