"""Tests of the benchmark itself, on the CPU at tiny sizes.

They sit outside the repository's ``tests/`` and run with
``python -m pytest chipbench/tests`` from the root of the checkout.
"""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "src"))

from chipbench.tests.support import make_tiny_root  # noqa: E402


@pytest.fixture
def tiny_root(tmp_path):
    return make_tiny_root(tmp_path)
