"""The benchmark's own tests of the cell `prefix_scan.p64r3`
(benchmarks/tests/test_prefix_scan_cell.py) as cases of the tier-1
run, which collects `tests/` alone. The module stays runnable by hand
as benchmarks/README.md says; its tests and its fixture carry `pscan`
in their names, so a later module imported beside it cannot shadow
them.

Not all of benchmarks/tests/ yet: its 58 cases take 205 s in one
process (PR 36, this sandbox's CPU), and under the driver's
`--dist loadfile` one file is one worker's; `test_rules_cell.py`
alone is ~100 s of it and holds cases with time limits of their own.
"""

from benchmarks.tests.test_prefix_scan_cell import *  # noqa: F401,F403
