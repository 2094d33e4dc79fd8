"""entry_host_ms_per_kop: host milliseconds per 1000 ops spent in
the checker entry outside the engine's chunk loop (per-key split,
encoding, result assembly): the benchmark's clock around each entry call,
less the window's delta of the program's jepsen_tpu_wgl_chunk_seconds."""


def read(run):
    return (run.check_s - run.chunk_s) * 1e6 / run.ops
