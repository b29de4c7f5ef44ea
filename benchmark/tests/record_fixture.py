#!/usr/bin/env python3
"""Record the trace fixture of test_trace_reduce.py, on the chip:

    python3 benchmark/tests/record_fixture.py --workload tpch_sf1.scan \\
        --seed 1 --seconds 5 --trace 1

One run of run.py with both profiler slices cut to SLICE_S and the
trace kept: benchmark/out/<cell>/trace/ holds the .xplane.pb and
trace_segments.json the marks, the segments and what the reduction
gave. Copy the two to benchmark/fixtures/scan_slice.xplane.pb and
scan_slice.json.
"""

import functools
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import run  # noqa: E402

SLICE_S = 0.4

if __name__ == "__main__":
    run.traced_slice = functools.partial(
        run.traced_slice, mix_s=SLICE_S, single_s=SLICE_S, keep=True)
    sys.exit(run.main())
