"""engine_chunk_ms_per_kop: milliseconds per 1000 ops in the engine's
chunk dispatch and lagged sync: the window's delta of the program's
jepsen_tpu_wgl_chunk_seconds, over every site."""


def read(run):
    if run.chunk_n == 0:
        return None
    return run.chunk_s * 1e6 / run.ops
