"""The reference rebuilds the served weights from the seed alone."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import cells
import families
import run
import weights
from smoke_cell import CONFIGS, smoke_cell


@pytest.mark.parametrize("config", CONFIGS)
def test_rebuilt_weights_equal_the_served_ones(config):
    cell = smoke_cell(config, "saturated")
    m = cell.model
    seed = 2 ** 33 + 17
    params = run.make_params(cells.model_config(cell), m, seed, "bfloat16")
    key = weights.root_key(seed)
    for i, layer in enumerate(params["layers"]):
        mine = weights.layer_weights(m, key, i, jnp.bfloat16)
        assert sorted(mine) == sorted(layer)
        for name in mine:
            np.testing.assert_array_equal(np.asarray(mine[name]),
                                          np.asarray(layer[name]))
    np.testing.assert_array_equal(
        np.asarray(weights.embed_table(m, key, jnp.bfloat16)),
        np.asarray(params["embed"]))
    np.testing.assert_array_equal(
        np.asarray(weights.final_norm(m, key, jnp.bfloat16)),
        np.asarray(params["final_norm"]))
    head = params.get("head", params["embed"])
    np.testing.assert_array_equal(
        np.asarray(weights.head_table(m, key, jnp.bfloat16)),
        np.asarray(head))
    assert m["qkv_bias"] == ("bq" in params["layers"][0])
    if m["qkv_bias"]:
        assert float(jnp.abs(params["layers"][0]["bk"]).max()) > 0


def test_root_key_takes_large_seeds():
    a = weights.root_key(5)
    b = weights.root_key(5 + 2 ** 32)
    assert not np.array_equal(jax.random.key_data(a), jax.random.key_data(b))
    with pytest.raises(ValueError):
        weights.root_key(-1)


# at the smoke cell's head_dim of 16, granite's 1/64 on the scores folds
# into q as 1/64 * sqrt(16)
@pytest.mark.parametrize("config,factors", [
    ("granite-3-2b", (0.015625 * 4, 0.22 / 12, 8.0)),
    ("qwen1.5-110b", (1.0, 1.0, 1.0))])
def test_checkpoint_undoes_the_folded_multipliers(config, factors):
    m = smoke_cell(config, "saturated").model
    np.testing.assert_allclose(weights.multiplier_factors(m), factors,
                               rtol=1e-12)
    key = weights.root_key(7)
    served = weights.layer_weights(m, key, 0, jnp.float32)
    ckpt = weights.checkpoint_layer(m, key, 0, jnp.float32)
    q, r, z = factors
    folded = families.of(m).FOLDED
    assert set(folded.values()) == {"q", "r"}
    for name in served:
        c = {"q": q, "r": r}.get(folded.get(name), 1.0)
        if c == 1.0:
            np.testing.assert_array_equal(ckpt[name], served[name])
        else:
            np.testing.assert_allclose(ckpt[name] * c, served[name],
                                       rtol=1e-6)
    fn = weights.final_norm(m, key, jnp.float32)
    np.testing.assert_allclose(
        (1 + weights.checkpoint_final_norm(m, key, jnp.float32)) / z, 1 + fn,
        rtol=1e-6)
