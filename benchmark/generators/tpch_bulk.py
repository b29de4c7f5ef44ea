"""generators/tpch.py's tables for a configuration whose set-up fits the
check's time only where the store ingests in bulk.

The data is tpch's, array for array: this module makes nothing of its
own. What it adds is a refusal. At SF10 a store that seeds a dictionary
one `Dictionary.encode` a value and summarizes the whole load as one
chunk on one core needs 364 s to ingest lineitem, 510 s of set-up in
all (chip run, PR 28, the program as of PR 26), and the check stops a
run at 360 s: such a program cannot run the configuration, and says so
in its first seconds with an exit code other than 0 instead of being
stopped at the limit. The mark of a bulk ingest is
`cockroach_tpu.storage.chunkstats.compute_many` (the chunks'
statistics built side by side, the larger part of those seconds); the
program keeps that name while a configuration names this generator.

The reference worker imports no part of the program, so there is
nothing to ask and nothing is refused there.
"""

from __future__ import annotations

import sys

from generators.tpch import DDL, TABLE_ORDER, generate as _generate  # noqa: F401

CHUNKSTATS = "cockroach_tpu.storage.chunkstats"


def require_bulk_ingest() -> None:
    stats = sys.modules.get(CHUNKSTATS)
    if stats is not None and not hasattr(stats, "compute_many"):
        raise SystemExit(
            "generators/tpch_bulk.py: this program's store has no bulk "
            f"ingest ({CHUNKSTATS}.compute_many): it ingests value by "
            "value and summarizes a load as one chunk, 364 s for "
            "lineitem at SF10, and the set-up would pass the check's "
            "time limit. Refusing to start it.")


def generate(table: str, sf: float, seed: int):
    """tpch.generate(), for a store that ingests in bulk."""
    require_bulk_ingest()
    return _generate(table, sf, seed)
