"""The benchmark's own tests of the cells `prefix_scan.p64r3`
(benchmarks/tests/test_prefix_scan_cell.py, test_prefix_scan_steps.py),
`ycsb_f.p64r3` (test_ycsb_f_cell.py) and `ttl_compact.p64r3`
(test_ttl_cell.py), of how a configuration's table is made
(test_table_loader.py), and of the trace reductions
(test_trace_reduce.py, test_trace_layers.py) as cases of the tier-1
run, which collects `tests/` alone. The modules
stay runnable by hand as benchmarks/README.md says; their tests and
fixtures have names of their own, so no module imported here shadows
another's. `pscan_later_metrics` joins the cell's later metrics to the
lists of test_prefix_scan_cell.py, as benchmarks/tests/conftest.py does
for a run by hand.

Not all of benchmarks/tests/ yet: its other cases take minutes in one
process on a CPU, and under `--dist loadfile` one file is one
worker's; `test_rules_cell.py` alone is ~100 s of it and holds cases
with time limits of their own.
"""

from benchmarks.tests.pscan_later_metrics import (  # noqa: F401
    pscan_later_metrics,
)
from benchmarks.tests.test_prefix_scan_cell import *  # noqa: F401,F403
from benchmarks.tests.test_prefix_scan_steps import *  # noqa: F401,F403
from benchmarks.tests.test_table_loader import *  # noqa: F401,F403
from benchmarks.tests.test_trace_layers import *  # noqa: F401,F403
from benchmarks.tests.test_trace_reduce import *  # noqa: F401,F403
from benchmarks.tests.test_ttl_cell import *  # noqa: F401,F403
from benchmarks.tests.test_ycsb_f_cell import *  # noqa: F401,F403
