"""kernel_ms_per_kop: milliseconds of device program time per 1000 ops,
over the checks of a traced run's window (PERF.md §3)."""


def read(run):
    if run.trace is None or run.ops == 0:
        return None
    return run.trace["program_s"] * 1e6 / run.ops
