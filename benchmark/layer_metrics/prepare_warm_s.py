"""Host prepare on a warm compile cache: over the cell's statement
classes, the first execution in set-up less that class's median in the
window. Read only on a run whose set-up compiled nothing (every program
came from the persistent cache), so that it is the host's planning,
tracing and cache loading and not the compiler."""


def read(ctx):
    if ctx["counters"]["setup"].get("exec.compile.cache_miss", 0) != 0:
        return None
    medians = ctx["client"]["class_median_ms"]
    total = 0.0
    for name, facts in ctx["setup"]["facts"].items():
        if name not in medians:
            return None
        total += facts["first_exec_s"][0] - medians[name] / 1e3
    return total
