#!/usr/bin/env python3
"""Record the fixture of test_span_reduce.py, on the chip:

    python3 benchmark/tests/record_span_fixture.py --workload \\
        tpch_sf1.scan --seed 1 --seconds 5 --trace 1

One traced run of run.py with every profiler slice cut to SLICE_S and
span_reduce's own trace kept: benchmark/out/<cell>/span_trace/ holds
the .xplane.pb, and span_segments.json the marks, the segments, the
collector's roots and what the reduction gave. Copy the two to
benchmark/fixtures/span_slice.xplane.pb and span_slice.json.
"""

import functools
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import run  # noqa: E402
import span_reduce  # noqa: E402

SLICE_S = 0.4

if __name__ == "__main__":
    span_reduce.KEEP = True
    span_reduce.MIX_S = span_reduce.SINGLE_S = SLICE_S
    run.traced_slice = functools.partial(
        run.traced_slice, mix_s=SLICE_S, single_s=SLICE_S)
    sys.exit(run.main())
