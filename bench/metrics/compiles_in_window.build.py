"""Executables compiled or loaded from the cache inside a build window
(JAX's compile events); none is expected."""


def read(record):
    if record["kind"] != "build":
        return None
    return record["compiles_in_window"]
