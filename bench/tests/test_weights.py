"""The benchmark's weights: the program's exact tree, one jitted call; a
layer rebuilt alone (as the reference does) equals its slice of the
whole tree bit for bit; seeds beyond 32 bits still change the draw."""
import jax
import jax.numpy as jnp
import numpy as np

from harness import spec, weights


def test_tree_matches_the_programs_layout(conf):
    from repro.models import model as M
    arch = spec.arch(conf)
    weights.check_layout(arch, conf, M.init_params, arch.model_config(conf))


def test_a_layer_alone_equals_its_slice(conf):
    arch = spec.arch(conf)
    key = weights.seed_key(2**31 + 5)
    whole = jax.jit(lambda k: arch.params(conf, k))(key)
    nk = arch.n_prefix(conf)
    for i in range(conf["num_hidden_layers"] - nk):
        alone = jax.jit(lambda k, j: arch.layer(conf, k, "block", j))(
            key, jnp.int32(i))
        sl = jax.tree.map(lambda x: x[i], whole["blocks"])
        for a, b in zip(jax.tree.leaves(alone), jax.tree.leaves(sl)):
            assert np.array_equal(np.asarray(a), np.asarray(b))
    if nk:
        alone = jax.jit(lambda k: arch.layer(conf, k, "prefix", 0))(key)
        for a, b in zip(jax.tree.leaves(alone),
                        jax.tree.leaves(whole["dense_prefix"][0])):
            assert np.array_equal(np.asarray(a), np.asarray(b))


def test_large_seeds():
    assert weights.fold32(7) == 7
    assert weights.fold32(2**31 + 7) == 2**31 + 7
    assert weights.fold32(2**32 + 7) != weights.fold32(7)
    k1 = np.asarray(weights.seed_key(2**31 + 9))
    k2 = np.asarray(weights.seed_key(9))
    assert not np.array_equal(k1, k2)
