"""Random weights from the run's seed, made by the benchmark.

The program serves them and the reference reads them; neither takes
weights the other made.  A configuration's architecture file
(``bench/arch/<arch>.py``) lays the tree out: its ``params(conf, key)``
builds the whole tree in the program's parameter layout, in the type the
configuration serves, and this module makes it in one jitted call on the
device (``program_init``) and holds it to the program's layout
(``check_layout``).

Every leaf is drawn from its own key, ``fold_in(key, leaf)`` of its part's
key, with ``normal`` / ``lin`` / ``ones``, so a layer's values do not
depend on which other layers are built with it.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """The legacy uint32[2] key for any whole-number seed.  Seeds under
    2**32 map to ``PRNGKey(seed)``; higher bits are folded in."""
    return jax.random.PRNGKey(fold32(seed))


def fold32(seed: int) -> int:
    seed = int(seed)
    out = 0
    while True:
        out ^= seed & 0xFFFFFFFF
        seed >>= 32
        if not seed:
            return out


def normal(key, leaf: int, shape, scale, dtype):
    """``N(0, scale^2)`` in ``dtype``, drawn from leaf number ``leaf`` of
    ``key``."""
    k = jax.random.fold_in(key, leaf)
    return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)


def ones(d, dtype):
    return {"scale": jnp.ones((d,), dtype)}


def lin(key, leaf: int, d_in, d_out, dtype):
    """A projection ``{"w": [d_in, d_out]}`` drawn ``N(0, 1/d_in)``."""
    return {"w": normal(key, leaf, (d_in, d_out), 1 / math.sqrt(d_in),
                        dtype)}


def program_init(arch, conf):
    """A stand-in for the program's ``init_params(cfg, rng, dtype)``: the
    program's boot calls it in one jitted call with its seed's key, which
    the harness sets to ``seed_key(seed)``."""
    def init(cfg, rng, dtype=None):
        return arch.params(conf, rng)
    return init


def check_layout(arch, conf, program_init_params, mcfg) -> None:
    """Raise unless the architecture file's tree has the program's
    structure, shapes and types exactly."""
    ours = jax.eval_shape(lambda: arch.params(conf, jax.random.PRNGKey(0)))
    theirs = jax.eval_shape(lambda: program_init_params(
        mcfg, jax.random.PRNGKey(0), jnp.dtype(mcfg.dtype)))
    a = jax.tree_util.tree_flatten_with_path(ours)
    b = jax.tree_util.tree_flatten_with_path(theirs)
    if a[1] != b[1]:
        raise SystemExit(f"weight tree differs from the program's: "
                         f"{a[1]} vs {b[1]}")
    for (path, x), (_, y) in zip(a[0], b[0]):
        if x.shape != y.shape or x.dtype != y.dtype:
            raise SystemExit(f"weight {jax.tree_util.keystr(path)}: "
                             f"{x.shape} {x.dtype} vs the program's "
                             f"{y.shape} {y.dtype}")
