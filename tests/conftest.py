import os

# Tests run on the single real CPU device; the multi-device checks set
# their own XLA_FLAGS in a subprocess and must NOT leak here.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("JAX_ENABLE_X64", "0")

import numpy as np
import pytest

import jax


def densify_rows(values, indices, n):
    """Independent numpy scatter oracle for fixed-width sparse rows: the one
    definition of "densified equal" the sparse-path suites assert against
    (deliberately NOT SparseFrontier.densify — the library under test).
    ``tests/parity_check.py`` keeps a private copy because it runs as a
    plain subprocess outside pytest's path setup."""
    values = np.asarray(values)
    q = values.shape[0]
    out = np.zeros((q, n), np.float32)
    np.add.at(
        out, (np.arange(q)[:, None], np.asarray(indices)), values
    )
    return out


def pytest_collection_modifyitems(config, items):
    """Auto-skip ``tpu``-marked tests off-TPU: they run the Pallas kernels
    with ``interpret=False``, which only a real TPU backend can compile."""
    if jax.default_backend() == "tpu":
        return
    skip = pytest.mark.skip(
        reason="needs a real TPU backend (interpret=False kernels)"
    )
    for item in items:
        if "tpu" in item.keywords:
            item.add_marker(skip)


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture()
def key():
    return jax.random.PRNGKey(0)
