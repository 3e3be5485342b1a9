"""Share of the traced window in which no operation ran on the device, in %,
for a build window (from the profiler trace)."""


def read(record):
    if record["kind"] != "build" or "trace" not in record:
        return None
    return 100.0 * record["trace"]["idle_share"]
