"""Per-arch smoke tests: reduced config, one real step per shape on CPU.

Asserts output shapes and absence of NaNs for every (arch x shape) cell.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import REGISTRY, get_arch

pytestmark = pytest.mark.slow  # one real train step per (arch x shape) cell
from repro.launch import steps as steps_mod
from repro.training import train_loop


def _finite(tree) -> bool:
    return all(
        bool(jnp.isfinite(x).all()) for x in jax.tree.leaves(tree)
        if jnp.issubdtype(x.dtype, jnp.floating)
    )


ALL_CELLS = [
    (spec.id, sh.name) for spec in REGISTRY.values() for sh in spec.shapes
]


@pytest.mark.parametrize("arch_id,shape_name", ALL_CELLS)
def test_smoke_cell(arch_id, shape_name):
    arch = get_arch(arch_id)
    bundle = steps_mod.build(arch, shape_name, reduced=True)
    key = jax.random.PRNGKey(0)
    params = bundle.init_fn(key)
    batch = bundle.make_batch(jax.random.PRNGKey(1))
    # batch matches its spec
    for name, sds in bundle.batch_spec.items():
        assert batch[name].shape == sds.shape, (name, batch[name].shape, sds.shape)
        assert batch[name].dtype == sds.dtype, name

    if bundle.kind == "train":
        opt_state = train_loop.init_state(bundle.opt_cfg or steps_mod.SMOKE_OPT, params)
        step = jax.jit(bundle.step_fn)
        new_params, new_opt, metrics = step(params, opt_state, batch)
        assert _finite(metrics), (arch_id, shape_name, metrics)
        assert float(metrics["loss"]) > 0.0
        assert _finite(new_params)
        # params actually changed
        changed = any(
            not np.allclose(np.asarray(a, np.float32), np.asarray(b, np.float32))
            for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(new_params))
        )
        assert changed
    else:
        if bundle.cache_spec is not None:
            cache = {
                k: jnp.zeros(v.shape, v.dtype)
                for k, v in bundle.cache_spec.items()
            }
            out = jax.jit(bundle.step_fn)(params, cache, batch)
            logits, new_cache = out
            assert _finite(logits)
            assert int(new_cache["length"]) == 1
        else:
            out = jax.jit(bundle.step_fn)(params, batch)
            assert _finite(out)


@pytest.mark.parametrize("arch_id", sorted(REGISTRY))
def test_second_train_step_decreases_or_close(arch_id):
    """Two steps on the first train-like shape: loss must not explode."""
    arch = get_arch(arch_id)
    train_shapes = [s for s in arch.shapes
                    if "train" in s.kind or s.kind.endswith("_full")]
    if not train_shapes:
        pytest.skip("no train shape")
    bundle = steps_mod.build(arch, train_shapes[0].name, reduced=True)
    if bundle.kind != "train":
        pytest.skip("serve-only cell")
    params = bundle.init_fn(jax.random.PRNGKey(0))
    opt_state = train_loop.init_state(bundle.opt_cfg or steps_mod.SMOKE_OPT, params)
    step = jax.jit(bundle.step_fn)
    batch = bundle.make_batch(jax.random.PRNGKey(1))
    p1, o1, m1 = step(params, opt_state, batch)
    p2, o2, m2 = step(p1, o1, batch)
    assert float(m2["loss"]) < float(m1["loss"]) * 1.5


def test_registry_covers_assignment():
    assert len(REGISTRY) == 10
    assert len(ALL_CELLS) == 40
