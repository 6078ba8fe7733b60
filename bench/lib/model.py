"""The system under test, built from a configuration file and its model
module (bench/models/<model>.py, which gives the program's architecture
spec and the weights' draw rule): its engine settings, and random weights
drawn by the benchmark itself from the seed.

The benchmark, not the program, makes the weights, in one jitted call on
the device and in the type they are served in.  The reference draws them
again with the same call once the program's state is freed, so the two
sides agree on every weight and the reference takes nothing the program
made.
"""
from __future__ import annotations

import functools
import math

import numpy as np


def engine_config(conf: dict, model, max_len: int, *, use_kernel: bool):
    """Engine settings: the configuration's lanes and KV pool (its byte
    budget over the engine's own page size, at the bytes a token's cache
    takes in ``model``, the configuration's model module); page size,
    pages per kernel step and chunk stay at ``EngineConfig``'s
    defaults."""
    from repro.runtime import CacheConfig, EngineConfig

    serving = conf["serving"]
    page = EngineConfig().cache.page_size
    page_bytes = model.kv_bytes_per_token(model.shapes(conf)) * page
    return EngineConfig(
        cache=CacheConfig(
            num_pages=int(serving["kv_pool_bytes"] // page_bytes),
            page_size=page, max_pages_per_seq=-(-max_len // page)),
        max_lanes=int(serving["max_lanes"]), use_kernel=use_kernel)


def seed_key(seed: int):
    """A PRNG key from any non-negative whole number, 64 bits and more."""
    import jax
    w = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.fold_in(jax.random.PRNGKey(int(w[0] >> 1)),
                              int(w[1] >> 1))


@functools.lru_cache(maxsize=None)
def _drawer(cfg, rule):
    """One jitted program that draws every weight of ``cfg`` by ``rule``
    (the items of a model module's ``FAN_IN``)."""
    import jax
    import jax.numpy as jnp
    from repro.models import model as M

    fan_in = dict(rule)
    flat, tree = jax.tree_util.tree_flatten_with_path(M.param_specs(cfg))
    missing = [jax.tree_util.keystr(path) for path, _ in flat
               if path[-1].key not in fan_in]
    if missing:
        raise ValueError(f"{cfg.name}: no draw rule for weights {missing}")
    plan = []
    for path, sds in flat:
        f = fan_in[path[-1].key]
        plan.append((None if f is None else 1.0 / math.sqrt(f(sds.shape)),
                     sds))

    @jax.jit
    def draw(key):
        out = []
        for i, (std, sds) in enumerate(plan):
            if std is None:
                out.append(jnp.ones(sds.shape, sds.dtype))
            else:
                x = jax.random.normal(jax.random.fold_in(key, i),
                                      sds.shape, jnp.float32)
                out.append((x * std).astype(sds.dtype))
        return out

    return draw, tree


def draw_params(cfg, seed: int, rule: dict):
    """The weights of ``cfg`` for ``seed``, on the default device, in the
    tree the program's ``init_params`` would give.  ``rule`` maps each
    weight's leaf name to its fan-in from its shape, N(0, 1/fan_in), or
    to None for ones; a leaf it does not name is an error."""
    draw, tree = _drawer(cfg, tuple(rule.items()))
    return tree.unflatten(draw(seed_key(seed)))
