"""Device busy time in the traced build window over the source chunks
(``build_source_batch`` sources each) completed in it, in ms (from the
profiler trace)."""


def read(record):
    if record["kind"] != "build" or "trace" not in record \
            or not record["chunks"]:
        return None
    return 1e3 * record["trace"]["busy_s"] / record["chunks"]
