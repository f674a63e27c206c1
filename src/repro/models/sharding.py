"""Sharding rules: parameter PartitionSpecs + activation constraints.

Conventions (mesh axes: optional "pod", then "data", "model"):
  * TP  — the "wide" dim of every projection is sharded over ``model``
          (attention heads, ffn columns, experts, vocab).
  * FSDP/ZeRO — the other matmul dim is sharded over ("pod","data"); the
          optimizer state inherits the same specs, giving ZeRO-3 layout.
  * stacked layer axes (from scan-over-layers) are never sharded.
  * activations: batch over ("pod","data"), sequence over "model"
          (sequence parallelism) for full-sequence passes; decode keeps the
          KV cache sharded (batch over data, sequence over model).

These are *requests*: `constrain`/`spec_for` drop axes that do not divide the
corresponding dim, so small smoke configs and batch-1 decode fall back to
replication instead of erroring.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

# parameter-name → (spec for trailing dims) tables.  Leading stacked layer
# axes are padded with None automatically.  "F" = fsdp axes, "M" = model.
_UP = ("F", "M")      # (d_in, d_out_wide)
_DOWN = ("M", "F")    # (d_in_wide, d_out)
_RULES = {
    # attention
    "wq": _UP, "wk": _UP, "wv": _UP, "wo": _DOWN,
    # mla
    "w_dkv": _UP, "w_kr": ("F", None), "w_ukv": (None, "M"),
    # glu mlp
    "w_gate": _UP, "w_up": _UP, "w_down": _DOWN,
    # moe (experts have a leading E dim sharded over model = EP)
    "router": ("F", None),
    "experts.w_gate": ("M", "F", None), "experts.w_up": ("M", "F", None),
    "experts.w_down": ("M", None, "F"),
    # rwkv6
    "wr": _UP, "wg": _UP,
    "mix_w1": ("F", None), "mix_w2": (None, None, None),
    "decay_w1": ("F", None), "decay_w2": (None, None),
    # mamba2
    "in_proj": _UP, "out_proj": _DOWN, "conv": (None, "M"),
    # embedding / head
    "embedding": ("M", "F"), "lm_head": ("F", "M"),
}


def _axes(mesh) -> tuple:
    return tuple(mesh.axis_names)


def fsdp_axes(mesh):
    names = _axes(mesh)
    return tuple(a for a in ("pod", "data") if a in names) or None


_POPULATION_MODE = False


class population_mode:
    """Context: the ('pod','data') axes hold population members, so every
    'F' (FSDP/data-parallel) request inside the model resolves to None —
    member-internal sharding is TP-only (the population IS the data axis)."""

    def __enter__(self):
        global _POPULATION_MODE
        self._prev = _POPULATION_MODE
        _POPULATION_MODE = True

    def __exit__(self, *exc):
        global _POPULATION_MODE
        _POPULATION_MODE = self._prev


def _resolve(sym, mesh):
    if sym == "F":
        return None if _POPULATION_MODE else fsdp_axes(mesh)
    if sym == "M":
        return "model" if "model" in _axes(mesh) else None
    return sym


def _axis_size(mesh, axis):
    if axis is None:
        return 1
    if isinstance(axis, tuple):
        size = 1
        for a in axis:
            size *= mesh.shape[a]
        return size
    return mesh.shape[axis]


def spec_for(path: str, shape, mesh) -> P:
    """Find the rule for a param path like 'segments.moe.attn.wq.w'."""
    parts = [p for p in path.split(".") if p not in ("w",)]
    rule = None
    for span in (2, 1):           # longer (more specific) matches win
        for i in range(len(parts) - span + 1):
            key = ".".join(parts[i:i + span])
            if key in _RULES:
                rule = _RULES[key]
        if rule is not None:
            break
    if rule is None:
        return P()
    dims = [_resolve(s, mesh) for s in rule]
    # left-pad with None for stacked layer axes
    dims = [None] * (len(shape) - len(dims)) + dims
    # drop any axis that does not divide its dim
    out = []
    for d, ax in zip(shape, dims):
        out.append(ax if ax is not None and d % _axis_size(mesh, ax) == 0 else None)
    return P(*out)


def _path_str(path) -> str:
    out = []
    for k in path:
        out.append(str(getattr(k, "key", getattr(k, "idx", k))))
    return ".".join(out)


def param_specs(params, mesh):
    """PartitionSpec pytree mirroring ``params`` (rules above)."""
    def one(path, leaf):
        return spec_for(_path_str(path), leaf.shape, mesh)
    return jax.tree_util.tree_map_with_path(one, params)


# ---------------------------------------------------------------------------
# activation constraints (mesh-context aware, divisibility-safe)
# ---------------------------------------------------------------------------


def constrain(x, *spec):
    """with_sharding_constraint that no-ops outside a mesh context and drops
    non-dividing axes. ``spec`` entries may be 'F'/'M' symbols."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return x
    dims = []
    for d, sym in zip(x.shape, spec):
        ax = _resolve(sym, mesh)
        if isinstance(ax, tuple):
            ax = tuple(a for a in ax if a in mesh.axis_names) or None
        elif ax is not None and ax not in mesh.axis_names:
            ax = None
        dims.append(ax if ax is not None and d % _axis_size(mesh, ax) == 0 else None)
    dims += [None] * (len(x.shape) - len(dims))
    return jax.lax.with_sharding_constraint(x, P(*dims))


def constrain_tree(params):
    """Constrain every leaf of a (layer-local) param subtree to its rule spec.

    Applied inside scan bodies: pinning the per-layer parameter sharding also
    pins the COTANGENT sharding in the backward pass, which turns XLA's
    per-layer full-tensor gradient all-reduces into reduce-scatters (§Perf
    iteration 1 — a 2-4x collective-bytes reduction on MoE/dense train).
    """
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return params

    def one(path, leaf):
        spec = spec_for(_path_str(path), leaf.shape, mesh)
        if all(s is None for s in spec):
            return leaf
        return jax.lax.with_sharding_constraint(leaf, spec)

    return jax.tree_util.tree_map_with_path(one, params)


def batch_spec(shape, mesh, *, leading_batch: bool = True):
    """NamedSharding spec for a host batch array: batch over ('pod','data')."""
    f = fsdp_axes(mesh)
    if f is None or shape[0] % _axis_size(mesh, f) != 0:
        f = None
    return P(f, *([None] * (len(shape) - 1)))
