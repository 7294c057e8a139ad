"""Logical-axis sharding rules (MaxText-style) with divisibility fallbacks.

Models annotate parameters and activations with *logical* axis names
("embed", "heads", "mlp", "vocab", "batch", "seq", "expert", ...).  At
launch time these are resolved against the physical mesh via RULES; any
logical axis whose dimension does not divide the mapped mesh-axis size
falls back to replication for that tensor **and the fallback is recorded**
(surfaced in the dry-run report, e.g. smollm's 15 heads on a 16-way model
axis).

``shard(x, *logical_axes)`` applies ``with_sharding_constraint`` when an
ambient mesh is set (``jax.set_mesh`` / ``with mesh:``) and is a no-op on a
single device, so the same model code runs in CPU smoke tests and in the
512-device dry-run.
"""
from __future__ import annotations

import logging
import threading
from collections import deque
from typing import Any, Mapping, Sequence

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

logger = logging.getLogger(__name__)

__all__ = [
    "RULES", "shard", "logical_to_spec", "resolve_param_specs", "pad_vocab",
    "fallback_log", "mesh_signature",
]

# logical axis -> mesh axis (or tuple of mesh axes). ``None`` = replicated.
# "data"-like axes compose the pod axis so pure DP crosses pods.
RULES: dict[str, Any] = {
    "batch": ("pod", "data"),
    "seq": None,            # activations: sequence stays unsharded by default
    "seq_res": None,        # residual stream: "model" = Megatron-style SP
    "seq_shard": "data",    # opt-in sequence sharding (long-context prefill)
    "embed": None,
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "mlp": "model",
    "vocab": "model",
    "expert": "model",
    "expert_mlp": None,
    "conv": None,
    "state": None,
    "rnn": "model",
    "layers": None,
    "stack": None,
    "cache_seq": None,
}

class _FallbackLog:
    """Bounded, lock-guarded record of ``(tensor_name, logical_axis, dim,
    mesh_axes)`` sharding fallbacks.

    ``logical_to_spec`` appends from whatever thread resolves a spec --
    on the serving path that means concurrent engine threads -- so the
    old bare module-level list both grew without bound and interleaved
    racily.  This keeps the last ``maxlen`` entries (the dry-run report
    deduplicates anyway) behind a lock; iteration snapshots under the
    lock so consumers never see a mid-append view."""

    def __init__(self, maxlen: int = 256):
        self._lock = threading.Lock()
        self._entries: deque = deque(maxlen=maxlen)
        self.dropped = 0  # appends evicted by the bound since last clear

    def append(self, entry: tuple) -> None:
        with self._lock:
            if len(self._entries) == self._entries.maxlen:
                self.dropped += 1
            self._entries.append(entry)

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self.dropped = 0

    def __iter__(self):
        with self._lock:
            return iter(tuple(self._entries))

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def __bool__(self) -> bool:
        return len(self) > 0


# record of (tensor_name, logical_axis, dim, mesh_axes) fallbacks, for the
# dry-run report.
fallback_log = _FallbackLog()


def mesh_signature(mesh=None) -> tuple:
    """Hashable topology signature for compile/warm-template registries.

    Templates recorded while serving on one device topology must not
    replay against another (a warm program compiled for a 4-device mesh
    is garbage on a 2-device one), so registries key their entries by
    this.  ``None`` describes the default single-program placement:
    backend platform + visible device count, which is what determines
    the compiled executable off-mesh."""
    if mesh is None:
        try:
            return ("default", jax.default_backend(),
                    jax.device_count())
        except Exception:  # pragma: no cover - uninitialized backend
            return ("default", "unknown", 1)
    devs = tuple(int(d.id) for d in np.asarray(mesh.devices).flat)
    return ("mesh", tuple(mesh.axis_names),
            tuple(int(mesh.shape[a]) for a in mesh.axis_names),
            devs, getattr(mesh.devices.flat[0], "platform", "?"))


def _mesh_axis_size(mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    size = 1
    for a in axes:
        if a not in mesh.shape:
            return 0  # axis not present on this mesh
        size *= mesh.shape[a]
    return size


def _present(mesh, axes):
    if axes is None:
        return None
    if isinstance(axes, str):
        axes = (axes,)
    have = tuple(a for a in axes if a in mesh.shape)
    if not have:
        return None
    return have if len(have) > 1 else have[0]


def logical_to_spec(
    logical: Sequence[str | None],
    shape: Sequence[int] | None = None,
    mesh=None,
    *,
    rules: Mapping[str, Any] | None = None,
    name: str = "?",
) -> P:
    """Map logical axis names to a PartitionSpec against ``mesh``.

    If ``shape`` is given, any axis whose dim is not divisible by the mapped
    mesh-axis size is replicated instead (logged fallback).
    """
    rules = dict(RULES, **(rules or {}))
    mesh = mesh or _ambient_mesh()
    out = []
    for i, ax in enumerate(logical):
        mapped = rules.get(ax) if ax is not None else None
        if mapped is None or mesh is None:
            out.append(None)
            continue
        mapped = _present(mesh, mapped)
        if mapped is None:
            out.append(None)
            continue
        size = _mesh_axis_size(mesh, mapped)
        if shape is not None and size and shape[i] % size != 0:
            fallback_log.append((name, ax, shape[i], mapped))
            logger.info("sharding fallback: %s axis %r dim %d !%% mesh %s",
                        name, ax, shape[i], mapped)
            out.append(None)
            continue
        out.append(mapped)
    # PartitionSpec forbids using the same mesh axis twice; keep the first.
    seen: set[str] = set()
    cleaned = []
    for ax in out:
        axes = (ax,) if isinstance(ax, str) else (ax or ())
        if any(a in seen for a in axes):
            cleaned.append(None)
            continue
        seen.update(axes)
        cleaned.append(ax)
    return P(*cleaned)


def _ambient_mesh():
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m


def shard(x, *logical: str | None, rules: Mapping[str, Any] | None = None):
    """Activation sharding constraint by logical axis names (no-op without
    an ambient mesh, e.g. in single-device smoke tests)."""
    mesh = _ambient_mesh()
    if mesh is None or np.prod(tuple(mesh.shape.values())) == 1:
        return x
    spec = logical_to_spec(logical, x.shape, mesh, rules=rules, name="act")
    return jax.lax.with_sharding_constraint(x, spec)


def resolve_param_specs(logical_tree, shapes_tree, mesh, *, rules=None):
    """Resolve a pytree of logical-axis tuples into PartitionSpecs.

    ``logical_tree`` and ``shapes_tree`` must be congruent pytrees where the
    logical leaves are tuples of axis names and shape leaves are
    ShapeDtypeStructs (or arrays).
    """
    paths = {}

    def resolve(path, logical, sds):
        name = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                        for p in path)
        spec = logical_to_spec(logical, sds.shape, mesh, rules=rules,
                               name=name)
        paths[name] = spec
        return spec

    return jax.tree_util.tree_map_with_path(
        resolve, logical_tree, shapes_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(a, (str, type(None))) for a in x))


def pad_vocab(vocab: int, tp: int, multiple: int = 128) -> int:
    """Megatron-style vocab padding: to a multiple of ``multiple * tp``."""
    q = multiple * max(tp, 1)
    return -(-vocab // q) * q
