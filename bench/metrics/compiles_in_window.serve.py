"""Executables compiled or loaded from the cache inside a serve window
(JAX's compile events); none is expected."""


def read(record):
    if record["kind"] != "serve":
        return None
    return record["compiles_in_window"]
