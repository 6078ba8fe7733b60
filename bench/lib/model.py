"""The system under test, built from a configuration file: the program's
architecture spec, its engine settings, and random weights drawn by the
benchmark itself from the seed.

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

from bench.lib.flops import kv_bytes_per_token, model_shapes

#: the fan-in of each weight, by leaf name, from its stacked shape
_FAN_IN = {
    "wq": lambda s: s[-3], "wk": lambda s: s[-3], "wv": lambda s: s[-3],
    "wo": lambda s: s[-3] * s[-2],
    "w_gate": lambda s: s[-2], "w_up": lambda s: s[-2],
    "w_down": lambda s: s[-2],
    "head": lambda s: s[-1],
}


def arch_config(conf: dict):
    """The program's ``ArchConfig`` for a llama-family configuration."""
    from repro.configs.base import ArchConfig

    if conf.get("hidden_act", "silu") != "silu":
        raise ValueError(f"{conf['name']}: only SwiGLU (silu) MLPs run")
    s = model_shapes(conf)
    return ArchConfig(
        name=conf["name"], family="dense", num_layers=s["L"],
        d_model=s["d"], num_heads=s["H"], num_kv_heads=s["Kv"],
        d_ff=s["ff"], vocab_size=s["V"], head_dim=s["hd"],
        rope_theta=float(conf["rope_theta"]),
        norm_eps=float(conf["rms_norm_eps"]),
        tie_embeddings=s["tied"], param_dtype=conf["torch_dtype"],
        compute_dtype=conf["torch_dtype"])


def engine_config(conf: dict, max_len: int, *, use_kernel: bool):
    """Engine settings: the configuration's lanes and KV pool (its byte
    budget over the engine's own page size); page size, pages per kernel
    step and chunk stay at ``EngineConfig``'s defaults."""
    from repro.runtime import CacheConfig, EngineConfig

    serving = conf["serving"]
    page = EngineConfig().cache.page_size
    page_bytes = kv_bytes_per_token(model_shapes(conf)) * page
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
def _drawer(cfg):
    """One jitted program that draws every weight of ``cfg``."""
    import jax
    import jax.numpy as jnp
    from repro.models import model as M

    flat, tree = jax.tree_util.tree_flatten_with_path(M.param_specs(cfg))
    plan = []
    for path, sds in flat:
        leaf = path[-1].key
        if leaf == "scale":
            plan.append((None, sds))
        elif leaf == "table":
            plan.append((1.0, sds))
        elif leaf in _FAN_IN:
            plan.append((1.0 / math.sqrt(_FAN_IN[leaf](sds.shape)), sds))
        else:
            raise ValueError(f"no draw rule for weight {path}")

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


def draw_params(cfg, seed: int):
    """The weights of ``cfg`` for ``seed``, on the default device, in the
    tree the program's ``init_params`` would give."""
    draw, tree = _drawer(cfg)
    return tree.unflatten(draw(seed_key(seed)))
