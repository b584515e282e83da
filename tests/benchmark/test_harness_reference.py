"""The plain reference against one update of the program's production train
step, at a small size on the CPU: on one device, and on four as a 2x2
``hier`` mesh (parameters once per node of 2, the bridge over ``pod``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchharness import cpu_devices, load, tiny_cell


@pytest.mark.parametrize("chips", [1, 4])
def test_one_update_matches_the_reference(chips):
    train = load("benchmark/drivers/train.py")
    tokens = load("benchmark/tokens.py")
    cell = tiny_cell(chips=chips)
    config, traffic = cell["config"], cell["traffic"]
    devices = cpu_devices(chips)
    prog = train.Program(config, traffic, devices)
    ref = prog.ref
    seed = 2 ** 31 + 5
    words = tokens.seed_words(seed)
    batch = tokens.batch(traffic, config["vocab_size"], seed, 0)
    state = prog.init(words)
    state, met = prog.step(state, {"tokens": jax.device_put(
        batch, prog.batch_sharding)})

    opt = config["optimizer"]
    with jax.default_device(devices[0]):
        params = ref.init_params(config, train._key(words))
        zeros = jax.tree.map(jnp.zeros_like, params)
        new, _, _, loss, gnorm, _ = jax.jit(
            lambda p, m, v, t: ref.train_step(config, opt, p, m, v, t,
                                              batch))(params, zeros, zeros, 1)
    np.testing.assert_allclose(float(met["loss"]), float(loss), rtol=1e-5)
    np.testing.assert_allclose(float(met["gnorm"]), float(gnorm), rtol=1e-4)
    want = jax.tree.leaves(ref.pack(new))
    have = jax.tree.leaves(state["params"])
    assert len(want) == len(have)
    lr = opt["lr"]
    for w, h in zip(want, have):
        w, h = np.asarray(w), np.asarray(h)
        assert w.shape == h.shape
        diff = np.abs(w - h)
        # an element whose gradient is zero to rounding may take Adam's
        # first step (+-lr) in the other direction; no more than that
        assert diff.max() <= 2.01 * lr
        assert np.mean(diff > 1e-6) < 1e-3
