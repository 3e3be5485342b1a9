"""Device busy time in the traced serving window over the batches completed
in it, in ms (from the profiler trace)."""


def read(record):
    if record["kind"] != "serve" or "trace" not in record \
            or not record["batches"]:
        return None
    return 1e3 * record["trace"]["busy_s"] / record["batches"]
