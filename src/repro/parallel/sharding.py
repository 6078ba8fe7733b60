"""Logical-axis sharding rules (DP / FSDP / TP / EP / SP) + param factory.

Model code never names physical mesh axes.  It uses *logical* axes:

  ``dp``    batch dim                -> ("pod", "data")
  ``fsdp``  ZeRO-3 param shard dim   -> ("pod", "data")
  ``tp``    tensor-parallel dim      -> ("model",)   (heads / d_ff / vocab / experts)
  ``sp``    sequence-parallel dim    -> ("model",)   (long KV / scores seq dim)
  ``None``  replicated

The translation is *divisibility-safe*: a logical axis is dropped for a
tensor dim that the mesh axis product does not divide (e.g. hymba's 25 heads
on a 16-way model axis).  This keeps every arch compilable on the fixed
production meshes without per-arch special-casing, at the cost of
replication where the math demands it — exactly what a production framework
must do.

``ParamFactory`` builds a parameter tree once and interprets it twice:
``mode="init"`` materializes jax arrays; ``mode="spec"`` yields
ShapeDtypeStructs and records the PartitionSpec for every leaf (used for the
AOT dry-run and for checkpoint metadata).
"""
from __future__ import annotations

import contextlib
import contextvars
import functools
import hashlib
import math
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

#: Sharding profiles (perf hillclimb lever — see EXPERIMENTS.md §Perf).
#:   megatron: TP over `model` for heads/ff/vocab + ZeRO-3 over `pod,data`
#:             (per-layer activation gathers; the paper-faithful baseline
#:             maps HERO clusters onto the model axis)
#:   fsdp:     pure ZeRO-3 over the whole mesh (no TP): weights gathered
#:             per layer instead of activations — wins when the per-device
#:             batch is large (train_4k)
#:   serve:    TP only, no weight sharding over data: weights resident
#:             per model-shard, zero weight gathers per token — the decode
#:             profile (weights must fit HBM/tp)
PROFILES = {
    "megatron": {
        "dp": ("pod", "data"),
        "fsdp": ("pod", "data"),
        "tp": ("model",),
        "sp": ("model",),
        "ep": ("model",),
    },
    "fsdp": {
        "dp": ("pod", "data", "model"),
        "fsdp": ("pod", "data", "model"),
        "tp": (),
        "sp": (),
        "ep": ("model",),
    },
    "serve": {
        "dp": ("pod", "data"),
        "fsdp": (),
        "tp": ("model",),
        "sp": ("model",),
        "ep": ("model",),
    },
}

LOGICAL_TO_PHYSICAL = PROFILES["megatron"]

_PROFILE: contextvars.ContextVar[str] = contextvars.ContextVar(
    "repro_profile", default="megatron")

_MESH: contextvars.ContextVar[Optional[Mesh]] = contextvars.ContextVar(
    "repro_mesh", default=None)


@contextlib.contextmanager
def sharding_profile(name: str):
    assert name in PROFILES, (name, list(PROFILES))
    tok = _PROFILE.set(name)
    try:
        yield
    finally:
        _PROFILE.reset(tok)


def current_profile() -> str:
    return _PROFILE.get()


@contextlib.contextmanager
def mesh_context(mesh: Optional[Mesh]):
    tok = _MESH.set(mesh)
    try:
        if mesh is not None:
            with mesh:
                yield mesh
        else:
            yield None
    finally:
        _MESH.reset(tok)


def current_mesh() -> Optional[Mesh]:
    return _MESH.get()


def _physical_axes(logical: Optional[str], mesh: Mesh) -> Tuple[str, ...]:
    if logical is None:
        return ()
    phys = PROFILES[_PROFILE.get()].get(logical, ())
    return tuple(a for a in phys if a in mesh.shape)


def logical_pspec(shape: Sequence[int], axes: Sequence[Optional[str]],
                  mesh: Mesh) -> P:
    """Divisibility-safe PartitionSpec for `shape` annotated with logical axes."""
    assert len(shape) == len(axes), (shape, axes)
    used: set = set()
    out: List[Any] = []
    for dim, name in zip(shape, axes):
        phys = tuple(a for a in _physical_axes(name, mesh)
                     if a not in used and mesh.shape[a] > 1)
        # keep only a prefix of the physical axes whose product divides dim
        kept: List[str] = []
        prod = 1
        for a in phys:
            if dim % (prod * mesh.shape[a]) == 0:
                kept.append(a)
                prod *= mesh.shape[a]
        if kept:
            used.update(kept)
            out.append(tuple(kept) if len(kept) > 1 else kept[0])
        else:
            out.append(None)
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def named_sharding(shape: Sequence[int], axes: Sequence[Optional[str]],
                   mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, logical_pspec(shape, axes, mesh))


def shard(x: jax.Array, *axes: Optional[str]) -> jax.Array:
    """with_sharding_constraint against the context mesh (no-op without one)."""
    mesh = current_mesh()
    if mesh is None:
        return x
    spec = logical_pspec(x.shape, axes, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# ---------------------------------------------------------------------------
# Parameter factory
# ---------------------------------------------------------------------------

def _stable_seed(path: str) -> int:
    return int.from_bytes(hashlib.sha256(path.encode()).digest()[:4], "little")


class Axes:
    """Opaque pytree leaf carrying a logical-axes annotation."""

    __slots__ = ("axes",)

    def __init__(self, axes: Tuple[Optional[str], ...]):
        self.axes = tuple(axes)

    def __repr__(self):
        return f"Axes{self.axes}"


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _normal_leaf(key, shape, std, dtype, sharding):
    """One N(0, std^2) leaf, drawn in f32 and cast in the same program, so
    the device holds only the cast result (an eager draw keeps the f32
    buffer alive beside it: 5.8 GB for a full-width stacked MLP leaf).

    The values are bit-identical to the eager ``normal(...) * std``: the
    draw ends in a multiply by sqrt(2), and XLA would re-associate that
    with ``* std`` into one scalar product, rounding differently.  The
    finiteness select (a no-op on normals) sits between the two."""
    x = jax.random.normal(key, shape, jnp.float32)
    x = (jnp.where(jnp.isfinite(x), x, 0.0) * std).astype(dtype)
    if sharding is not None:
        x = jax.lax.with_sharding_constraint(x, sharding)
    return x


#: Placement of one parameter leaf: (factory path, full shape) -> sharding.
LeafSharding = Callable[[str, Tuple[int, ...]], Optional[jax.sharding.Sharding]]


class ParamFactory:
    """Single-definition parameter builder with three interpretations.

    mode="init": returns concrete arrays (normal / zeros / ones), each placed
                 by ``sharding`` (default device when it is None).
    mode="spec": returns ShapeDtypeStruct leaves.
    mode="axes": returns `Axes` leaves (tree mirrors the params tree, so no
                 fragile path matching is needed to pair specs with axes).
    """

    def __init__(self, mode: str, dtype: jnp.dtype, rng: Optional[jax.Array] = None,
                 sharding: Optional[LeafSharding] = None):
        assert mode in ("init", "spec", "axes")
        self.mode = mode
        self.dtype = dtype
        self.rng = rng
        self.sharding = sharding
        self._scope: List[str] = []
        self._stack: List[int] = []   # stacked-layer prefixes
        self.axes_by_path: Dict[str, Tuple[Optional[str], ...]] = {}

    @contextlib.contextmanager
    def scope(self, name: str):
        self._scope.append(name)
        try:
            yield
        finally:
            self._scope.pop()

    @contextlib.contextmanager
    def stacked(self, n: int):
        """Within this context every param gets a leading (n,) stack dim."""
        self._stack.append(n)
        try:
            yield
        finally:
            self._stack.pop()

    def _path(self, name: str) -> str:
        return "/".join(self._scope + [name])

    def __call__(self, name: str, shape: Sequence[int],
                 axes: Sequence[Optional[str]], init: str = "normal",
                 fan_in: Optional[int] = None, dtype=None,
                 fill: float = 0.0) -> Any:
        dtype = dtype or self.dtype
        full_shape = tuple(self._stack) + tuple(shape)
        full_axes = (None,) * len(self._stack) + tuple(axes)
        path = self._path(name)
        self.axes_by_path[path] = full_axes
        if self.mode == "axes":
            return Axes(full_axes)
        if self.mode == "spec":
            return jax.ShapeDtypeStruct(full_shape, dtype)
        # constant inits go through numpy fp32 + on-device cast so every leaf
        # owns a distinct buffer — jnp constants may alias, which breaks
        # donated train state ("attempt to donate the same buffer twice")
        import numpy as _np
        where = self.sharding(path, full_shape) if self.sharding else None
        if init in ("zeros", "ones", "fill"):
            val = {"zeros": 0.0, "ones": 1.0, "fill": fill}[init]
            base = jax.device_put(_np.full(full_shape, val, _np.float32), where)
            return base.astype(dtype)
        key = jax.random.fold_in(self.rng, _stable_seed(path))
        fi = fan_in if fan_in is not None else (shape[0] if shape else 1)
        std = 1.0 / math.sqrt(max(fi, 1))
        return _normal_leaf(key, full_shape, std, jnp.dtype(dtype), where)


def is_axes_leaf(x: Any) -> bool:
    return isinstance(x, Axes)


def tree_pspecs(spec_tree: Any, axes_tree: Any, mesh: Mesh) -> Any:
    """PartitionSpec tree from a (ShapeDtypeStruct tree, Axes tree) pair."""
    return jax.tree.map(
        lambda sds, ax: logical_pspec(sds.shape, ax.axes, mesh),
        spec_tree, axes_tree, is_leaf=lambda x: is_axes_leaf(x))


def tree_shardings(spec_tree: Any, axes_tree: Any, mesh: Mesh) -> Any:
    return jax.tree.map(
        lambda sds, ax: NamedSharding(mesh, logical_pspec(sds.shape, ax.axes, mesh)),
        spec_tree, axes_tree, is_leaf=lambda x: is_axes_leaf(x))


# ---------------------------------------------------------------------------
# Sharded paged-serving engine specs (the ClusterMesh ("cluster", "head"))
# ---------------------------------------------------------------------------
#
# The paged engine shards two ways: request lanes (and their KV page shard)
# over ``cluster``, attention heads GQA-aware over ``head``.  Everything
# else — embeddings, norms, MLP/MoE weights, the logits path — is
# replicated so on-device greedy sampling needs no cross-shard collective
# beyond the attention-output psum.

#: attention param leaves sharded over the head axis: leaf name -> the
#: negative index of its head dimension (stacked-layer leading dims make
#: positive indices unstable).  wq/wk/wv: (..., d, H|Kv, hd); wo:
#: (..., H, hd, d); q_norm/k_norm: (..., H|Kv, hd).
_HEAD_DIM_BY_NAME = {"wq": -2, "wk": -2, "wv": -2, "wo": -3,
                     "q_norm": -2, "k_norm": -2}


def _leaf_name(path) -> str:
    last = path[-1]
    return getattr(last, "key", None) or getattr(last, "name", "") or str(last)


def _head_pspec(name: str, ndim: int, head_axis: str) -> P:
    dim = _HEAD_DIM_BY_NAME.get(name)
    if dim is None:
        return P()
    out = [None] * ndim
    out[ndim + dim] = head_axis
    return P(*out)


def head_param_pspecs(params: Any, head_axis: str = "head") -> Any:
    """PartitionSpec tree sharding attention heads over ``head_axis``.

    GQA-aware by construction: the reference/kernel head layout is
    kv-major (query head ``k*G + g`` attends through kv head ``k``), so
    splitting both the H and Kv dims into equal contiguous blocks keeps
    every query head co-resident with its kv head.  The caller must check
    the axis size divides ``num_kv_heads`` (see
    ``kernels.paged_attention.ops.validate_head_sharding``).
    """
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: _head_pspec(_leaf_name(path), leaf.ndim,
                                       head_axis), params)


def head_param_sharding(mesh: Mesh, head_axis: str = "head") -> LeafSharding:
    """``ParamFactory`` placement that draws every leaf straight into its
    ``head_param_pspecs`` sharding on ``mesh``, so no device ever holds a
    leaf it does not keep."""
    return lambda path, shape: NamedSharding(
        mesh, _head_pspec(path.rsplit("/", 1)[-1], len(shape), head_axis))


def cluster_engine_specs(params: Any) -> Dict[str, Any]:
    """Spec pieces for ``shard_map``-ing the paged engine steps.

    ``kv`` is the fused (L, C*(P+1), 2, page, Kv, hd) slab — pages sharded
    over ``cluster`` (each cluster's contiguous block includes its own
    trash page), kv heads over ``head``; ``kv_scales`` is its
    (L, C*(P+1), 2, Kv) per-page dequant-scale companion for the int8 KV
    mode, sharded the same two ways; ``lane``/``lane2`` shard lane-indexed
    (B,) / (B, n) arrays' leading batch dim over ``cluster``; ``params`` is
    the head-sharded attention-weight tree.  The engine step returns
    (sampled, kv_pages, kv_scales, new_lens) ->
    (lane, kv, kv_scales, lane).
    """
    return {
        "params": head_param_pspecs(params),
        "kv": P(None, "cluster", None, None, "head", None),
        "kv_scales": P(None, "cluster", None, "head"),
        "lane": P("cluster"),
        "lane2": P("cluster", None),
    }
