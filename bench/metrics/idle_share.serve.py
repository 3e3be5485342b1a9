"""Share of the traced window in which no operation ran on the device, in %,
for a serve window (from the profiler trace)."""


def read(record):
    if record["kind"] != "serve" or "trace" not in record:
        return None
    return 100.0 * record["trace"]["idle_share"]
