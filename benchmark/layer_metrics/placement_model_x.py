"""The placement model over what the chip held: the largest working set
`exec/scanplane.py:_stream_decision` weighed against the HBM budget in
set-up (gauge `sql.exec.placement.model_bytes.max`: a statement's
pruned upload plus what its aggregation path allocates) over the
fullest chip's `peak_bytes_in_use` + `peak_bytes_reserved` after the
window. The model prices one statement and the peak also holds what
set-up left resident while it replaced it, so the ratio is under 1;
a model that drifts from the program shows as a ratio that moves.
Left out where the program has no such gauge or JAX no memory
statistics (the CPU)."""

GAUGE = "sql.exec.placement.model_bytes.max"


def read(ctx):
    model = ctx["counters"]["setup"].get(GAUGE)
    memory = ctx["memory"]
    if not model or not memory or not memory["peak_bytes"]:
        return None
    return model / memory["peak_bytes"]
