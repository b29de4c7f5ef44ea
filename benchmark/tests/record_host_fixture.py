#!/usr/bin/env python3
"""Record the fixture of test_host_reduce.py, on the chip:

    python3 benchmark/tests/record_host_fixture.py --workload \\
        tpch_sf1.scan --seed 1 --seconds 5 --trace 1

One traced run of run.py with every slice cut to SLICE_S and
host_reduce's roots kept: benchmark/out/<cell>/host_roots.json holds
the served roots of its slice in wire form (spans with `u`, stage marks
`g`), the classes, the slice's bounds and what the reduction gave. Copy
it to benchmark/fixtures/host_roots.json.
"""

import functools
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import host_reduce  # noqa: E402
import run  # noqa: E402
import span_reduce  # noqa: E402

SLICE_S = 0.2

if __name__ == "__main__":
    host_reduce.KEEP = True
    host_reduce.MIX_S = SLICE_S
    span_reduce.MIX_S = span_reduce.SINGLE_S = SLICE_S
    run.traced_slice = functools.partial(
        run.traced_slice, mix_s=SLICE_S, single_s=SLICE_S)
    sys.exit(run.main())
