"""Logical-axis sharding context.

Model code annotates activations with *logical* axes ("dp", "tp", "sp");
the launcher binds them to physical mesh axes (("pod","data"), "model",
"data") once, so the same model code runs on the single-pod (data, model)
mesh, the multi-pod (pod, data, model) mesh, or a single CPU device
(no-op). Parameters carry logical PartitionSpecs built with ``lspec``;
``resolve_pspec`` translates them to physical PartitionSpecs.
"""

from __future__ import annotations

import contextlib
import threading

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

#: logical axis names used throughout the model code
DP = "dp"        # data parallel (batch) — maps to ("pod","data") or ("data",)
TP = "tp"        # tensor parallel — maps to "model"
FSDP = "fsdp"    # parameter sharding — maps to "data" (and "pod" if desired)
SP = "sp"        # sequence parallel (long-context) — maps to "data"
EP = "ep"        # expert parallel — maps to "model"

_state = threading.local()


def _ctx():
    if not hasattr(_state, "stack"):
        _state.stack = []
    return _state.stack


@contextlib.contextmanager
def logical_axis_rules(mesh: jax.sharding.Mesh | None, rules: dict[str, tuple[str, ...] | str | None]):
    """Bind logical axes to physical mesh axes for the duration of a trace.

    rules maps logical name -> physical axis (str), tuple of axes, or None
    (replicate). Unknown logical names replicate.
    """
    _ctx().append((mesh, dict(rules)))
    try:
        yield
    finally:
        _ctx().pop()


def current_rules():
    stack = _ctx()
    return stack[-1] if stack else (None, {})


def unsharded() -> bool:
    """Whether no multi-device mesh is bound, neither the model's
    logical-axis rules nor JAX's mesh context."""
    mesh, _ = current_rules()
    return (mesh is None or mesh.size <= 1) and jax.sharding.get_abstract_mesh().size <= 1


def default_rules(multi_pod: bool) -> dict:
    if multi_pod:
        return {DP: ("pod", "data"), TP: "model", FSDP: "data", SP: "data", EP: "model"}
    return {DP: ("data",), TP: "model", FSDP: "data", SP: "data", EP: "model"}


def resolve_pspec(logical: tuple, rules: dict) -> P:
    """Translate a tuple of logical axis names (or None / tuples) into a
    physical PartitionSpec under the given rules."""
    phys = []
    for ax in logical:
        if ax is None:
            phys.append(None)
        elif isinstance(ax, (tuple, list)):
            merged: list[str] = []
            for a in ax:
                m = rules.get(a)
                if m is None:
                    continue
                merged.extend(m if isinstance(m, (tuple, list)) else (m,))
            phys.append(tuple(merged) if merged else None)
        else:
            m = rules.get(ax)
            if m is None:
                phys.append(None)
            elif isinstance(m, (tuple, list)):
                phys.append(tuple(m))
            else:
                phys.append(m)
    # PartitionSpec forbids duplicate mesh axes; drop later repeats
    seen: set[str] = set()
    out = []
    for entry in phys:
        if entry is None:
            out.append(None)
        elif isinstance(entry, tuple):
            keep = tuple(a for a in entry if a not in seen)
            seen.update(keep)
            out.append(keep if keep else None)
        else:
            if entry in seen:
                out.append(None)
            else:
                seen.add(entry)
                out.append(entry)
    return P(*out)


def shard_hint(x: jax.Array, *logical_axes) -> jax.Array:
    """with_sharding_constraint against the current logical-axis binding;
    identity when no mesh is bound (CPU tests)."""
    mesh, rules = current_rules()
    if mesh is None:
        return x
    spec = resolve_pspec(tuple(logical_axes), rules)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def named_sharding(mesh: jax.sharding.Mesh, rules: dict, logical: tuple) -> NamedSharding:
    return NamedSharding(mesh, resolve_pspec(logical, rules))
