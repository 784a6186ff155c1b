"""Fixtures every module of the benchmark's own tests sees."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.tests.pscan_later_metrics import (  # noqa: E402,F401
    pscan_later_metrics,
)
