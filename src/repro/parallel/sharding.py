"""Sharding rules: logical axes -> physical mesh axes, with divisibility
fallbacks.

Logical axis vocabulary (resolved against whatever axes the active mesh
actually has, so the same rules serve the single-pod (data, model) and the
multi-pod (pod, data, model) meshes):

  batch   -> (pod, data)    activations' batch / the MPSL client axis
  fsdp    -> (data,)        weight sharding within a pod (ZeRO/FSDP)
  model   -> (model,)       tensor parallelism (heads / ff / vocab / experts)
  dboth   -> (data, model)  fully-sharded fallback for a contraction dim

Every rule is a chain of candidates per tensor dim; the first candidate
whose mesh-axis product divides the dim wins, else the dim is unsharded.
This is what makes one rule set work for 24-head and 64-head archs alike.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Any, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import obs

LOGICAL = {
    "batch": ("pod", "data"),
    "client": ("pod", "data"),
    "fsdp": ("data",),
    "model": ("model",),
    "dboth": ("data", "model"),
    "pod": ("pod",),
}

_state = threading.local()


def current_mesh() -> Optional[Mesh]:
    return getattr(_state, "mesh", None)


@contextlib.contextmanager
def use_mesh(mesh: Optional[Mesh]):
    """Set the active mesh for shard_act / rule resolution. All shardings
    are built as explicit NamedShardings, so jax's own mesh context is not
    entered (this also lets AbstractMesh be used in tests)."""
    prev = current_mesh()
    _state.mesh = mesh
    try:
        yield mesh
    finally:
        _state.mesh = prev


def _axes_in_mesh(mesh: Mesh, logical: str) -> Tuple[str, ...]:
    return tuple(a for a in LOGICAL[logical] if a in mesh.axis_names)


def _axes_size(mesh: Mesh, axes: Tuple[str, ...]) -> int:
    return int(np.prod([mesh.shape[a] for a in axes], dtype=np.int64)) \
        if axes else 1


def resolve_dim(mesh: Mesh, dim: int, candidates) -> Optional[Any]:
    """candidates: None | str | sequence of str (fallback chain)."""
    if candidates is None:
        return None
    if isinstance(candidates, str):
        candidates = (candidates,)
    for logical in candidates:
        axes = _axes_in_mesh(mesh, logical)
        size = _axes_size(mesh, axes)
        if axes and size > 1 and dim % size == 0:
            return axes if len(axes) > 1 else axes[0]
    return None


def resolve_spec(mesh: Mesh, shape: Sequence[int], dims) -> P:
    assert len(dims) == len(shape), (dims, shape)
    return P(*[resolve_dim(mesh, d, c) for d, c in zip(shape, dims)])


def named(mesh: Mesh, shape: Sequence[int], dims) -> NamedSharding:
    return NamedSharding(mesh, resolve_spec(mesh, shape, dims))


def shard_act(x, dims):
    """with_sharding_constraint against the active mesh (no-op off-mesh)."""
    mesh = current_mesh()
    if mesh is None or mesh.size == 1:
        return x
    return jax.lax.with_sharding_constraint(x, named(mesh, x.shape, dims))


# ---------------------------------------------------------------------------
# Host batch placement (prefetch pipeline)


def batch_specs(batch, mesh: Mesh):
    """Per-leaf PartitionSpecs for an MPSL host batch: the leading axis of
    every array is the client axis -> sharded over the mesh data axes when
    divisible, everything else replicated."""
    def rule(leaf):
        shape = tuple(np.shape(leaf))
        dims = ("client",) + (None,) * (len(shape) - 1)
        return resolve_spec(mesh, shape, dims)
    return jax.tree_util.tree_map(rule, batch)


def place_batch(batch, mesh: Optional[Mesh] = None):
    """``device_put`` a host batch directly onto the mesh's client/batch
    layout (no uncommitted transfer + reshard at trace time). Off-mesh, a
    plain committed ``device_put`` — still useful, because running it on
    the prefetch thread overlaps H2D with device compute.

    The ``h2d/place_batch`` span measures dispatch of the transfer (the
    device_put calls are async); it is a host-boundary wall-clock span
    and introduces no device sync."""
    with obs.span("h2d/place_batch"):
        mesh = mesh if mesh is not None else current_mesh()
        if mesh is None or mesh.size == 1:
            dev = (mesh.devices.flat[0] if mesh is not None
                   else jax.local_devices()[0])
            return jax.tree_util.tree_map(
                lambda v: jax.device_put(np.asarray(v), dev), batch)
        return jax.tree_util.tree_map(
            lambda v, spec: jax.device_put(np.asarray(v),
                                           NamedSharding(mesh, spec)),
            batch, batch_specs(batch, mesh))


# ---------------------------------------------------------------------------
# Parameter sharding rules (path-based)


def _param_dims(path: Tuple[str, ...], shape: Tuple[int, ...]):
    """Rule table: (parent..., leaf) names + shape -> per-dim candidates."""
    # --- MPSL client heads: stacked [N, ...] over the client axis -----------
    if "adapter" in path or "tokenizers" in path:
        return ("client",) + (None,) * (len(shape) - 1)

    # --- scan segments: stacked [L_seg, ...] — rules apply past the layer dim
    if "segments" in path and len(shape) >= 1:
        return (None,) + tuple(_param_dims_base(path, shape[1:]))
    return _param_dims_base(path, shape)


def _param_dims_base(path: Tuple[str, ...], shape: Tuple[int, ...]):
    leaf = path[-1]
    parent = path[-2] if len(path) > 1 else ""

    # --- embeddings / heads -------------------------------------------------
    if leaf == "table":                       # [V, D]
        return ("fsdp", "model")
    if leaf == "lm_head":                     # [D, V]
        return ("fsdp", "model")
    if leaf == "pos":                         # [S, D]
        return (None, "model")

    # --- attention ----------------------------------------------------------
    if leaf in ("wq", "wk", "wv"):            # [D, H|K, hd]
        if shape[1] % _model_size() == 0:     # TP over heads, FSDP over D
            return ("fsdp", "model", None)
        # heads not divisible: fully shard the contraction dim instead
        return (("dboth", "model"), None, None)
    if leaf == "wo" and len(shape) == 3 and parent != "moe":
        # attention output [H, hd, D]
        if shape[0] % _model_size() == 0:
            return ("model", None, "fsdp")
        return (None, None, ("dboth", "model"))
    if leaf in ("bq", "bk", "bv"):            # [H|K, hd]
        if shape[0] % _model_size() == 0:
            return ("model", None)
        return (None, None)

    # --- MoE (3D expert-stacked weights) -------------------------------------
    if len(shape) == 3 and leaf in ("wi", "wg"):      # [E, D, F]
        if shape[0] % _model_size() == 0:             # expert parallelism
            return ("model", "fsdp", None)
        return (None, "fsdp", "model")                # TP on F fallback
    if len(shape) == 3 and leaf == "wo":              # [E, F, D]
        if shape[0] % _model_size() == 0:
            return ("model", None, "fsdp")
        return (None, "model", "fsdp")
    if leaf == "router":                      # [D, E]
        return ("fsdp", None)
    if leaf == "shared_gate":                 # [D, 1]
        return ("fsdp", None)

    # --- dense MLP ----------------------------------------------------------
    if leaf in ("wi", "wg") and len(shape) == 2:   # [D, F]
        return ("fsdp", "model")
    if leaf == "wo" and len(shape) == 2:           # [F, D]
        return ("model", "fsdp")

    # --- Mamba (parent == 'ssm') ---------------------------------------------
    if leaf == "in_proj":                     # [D, 2*di]
        return ("fsdp", "model")
    if leaf == "conv_w":                      # [dc, di]
        return (None, "model")
    if leaf in ("conv_b", "dt_bias", "D"):    # [di]
        return ("model",)
    if leaf == "x_proj":                      # [di, dtr+2ds]
        return ("model", None)
    if leaf == "dt_proj":                     # [dtr, di]
        return (None, "model")
    if leaf == "A_log":                       # [di, ds]
        return ("model", None)
    if leaf == "out_proj":                    # [di, D]
        return ("model", "fsdp")

    # --- tokenizers / misc ---------------------------------------------------
    if leaf == "embed" and len(shape) == 2:   # text tokenizer table [V, D]
        return ("fsdp", "model")
    if leaf == "proj" and len(shape) == 2:    # patch proj [p*p*c, D]
        return (None, "model")

    # norms, biases, scalars, cls, betas: replicate
    return tuple(None for _ in shape)


def _model_size() -> int:
    mesh = current_mesh()
    return int(mesh.shape["model"]) if mesh is not None \
        and "model" in mesh.axis_names else 1


def _path_names(key_path) -> Tuple[str, ...]:
    names = []
    for k in key_path:
        if hasattr(k, "key"):
            names.append(str(k.key))
        elif hasattr(k, "idx"):
            names.append(str(k.idx))
        elif hasattr(k, "name"):
            names.append(str(k.name))
        else:
            names.append(str(k))
    return tuple(names)


def param_specs(params, mesh: Mesh):
    """Pytree of PartitionSpecs mirroring `params`."""
    def rule(key_path, leaf):
        path = _path_names(key_path)
        shape = tuple(getattr(leaf, "shape", ()))
        with use_mesh(mesh):
            dims = _param_dims(path, shape)
            return resolve_spec(mesh, shape, dims)
    return jax.tree_util.tree_map_with_path(rule, params)


def param_shardings(params, mesh: Mesh):
    return jax.tree_util.tree_map(
        lambda spec: NamedSharding(mesh, spec), param_specs(params, mesh),
        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# Cache sharding


def cache_dims(shape: Tuple[int, ...], leaf: str, stacked: bool,
               kv_heads: Optional[int] = None):
    """KV cache [L?, B, S, K, hd] / pos [L?, B, S] / ssm h [L?, B, di, ds].

    When the KV heads don't divide the TP axis, the cache SEQ dim is
    sharded over `model` instead — `pos` must then follow the same seq
    sharding so decode masks stay local."""
    lead = ("__layer__",) if stacked else ()
    n = len(shape) - len(lead)
    if leaf in ("k", "v") and n == 4:
        _, _, k_heads, _ = shape[-4:]
        kv = "model" if k_heads % _model_size() == 0 else None
        seq = None if kv else "model"
        return (None,) * len(lead) + ("batch", seq, kv, None)
    if leaf == "pos" and n == 2:
        seq = None if (kv_heads is not None
                       and kv_heads % _model_size() == 0) else "model"
        return (None,) * len(lead) + ("batch", seq)
    if leaf == "index":
        return (None,) * len(shape)
    if leaf == "h" and n == 3:                # [B, di, ds]
        return (None,) * len(lead) + ("batch", "model", None)
    if leaf == "conv" and n == 3:             # [B, dc-1, di]
        return (None,) * len(lead) + ("batch", None, "model")
    return tuple(None for _ in shape)
