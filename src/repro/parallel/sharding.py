"""Logical-axis sharding rules (DP / FSDP / TP / EP / SP on one mesh).

Mesh axes:
  pod   — cross-datacenter data parallelism (the paper's WAN boundary).
  data  — in-pod data parallelism; also the FSDP axis for parameters.
  model — tensor parallelism (heads / mlp / experts / vocab) and, for
          long-context serving, sequence parallelism of the KV cache.

Activations use *logical* names resolved through ACTIVATION_RULES; parameters
are matched by path pattern in :func:`param_partition_spec`.  Everything is a
no-op when no mesh context is active, so the same model code runs single-host.
"""
from __future__ import annotations

import contextlib
import re
import threading
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_state = threading.local()


def site_mesh(n_devices: Optional[int] = None) -> Mesh:
    """1-D mesh over the fleet's embarrassingly-parallel site axis.

    The batched (E, k, N) planning stack splits along E across all local
    devices (or the first ``n_devices``); only the controller's (E,)
    demand/budget vectors ever cross hosts, so a plain device list is the
    whole topology."""
    import numpy as np
    devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    return Mesh(np.asarray(devices), ("sites",))


def site_pad(n_sites: int, n_devices: int) -> int:
    """Rows to append so the site axis divides the device count."""
    return (-int(n_sites)) % int(n_devices)


def pad_site_axis(x, n_padded: int, fill=0):
    """Pad a site-leading array with ``fill`` rows up to ``n_padded`` sites.

    Shared by the sharded plan engine and the sharded scan runtime so every
    shard_map consumer rounds E up the same way; callers mask the extra
    rows as permanently-dead sites (``repro.chaos.padded_liveness_table``)
    or slice them back off the result.
    """
    e = x.shape[0]
    if int(n_padded) == e:
        return x
    pad = jnp.full((int(n_padded) - e,) + tuple(x.shape[1:]), fill, x.dtype)
    return jnp.concatenate([x, pad])


# ---------------------------------------------------------------------------
# the site mesh's cross-device exchange
# ---------------------------------------------------------------------------

EXCHANGE_SCOPE = "exchange"
EXCHANGE_COUNTERS = ("exchange_all_gathers", "exchange_all_reduces",
                     "exchange_gather_bytes")


def _tally(counter: str, n: int) -> None:
    counts = getattr(_state, "exchange", None)
    if counts is not None:
        counts[counter] += int(n)


@contextlib.contextmanager
def count_exchange():
    """Count the site-mesh collectives traced while open: yields
    ``{counter: n}`` over :data:`EXCHANGE_COUNTERS`, the all-gathers, the
    all-reduces and the bytes each device receives from its all-gathers.
    Traced once per window step, they are the step's counts a window."""
    prev = getattr(_state, "exchange", None)
    _state.exchange = counts = dict.fromkeys(EXCHANGE_COUNTERS, 0)
    try:
        yield counts
    finally:
        _state.exchange = prev


def exchange_all_gather(x, axis_name: str):
    """``all_gather(tiled=True)`` over the site mesh, under the
    ``exchange`` scope (op metadata only) and counted."""
    with jax.named_scope(EXCHANGE_SCOPE):
        out = jax.lax.all_gather(x, axis_name, tiled=True)
    _tally("exchange_all_gathers", 1)
    _tally("exchange_gather_bytes", out.size * out.dtype.itemsize)
    return out


def exchange_pmax(x, axis_name: str):
    """``pmax`` over the site mesh, under the ``exchange`` scope and
    counted."""
    with jax.named_scope(EXCHANGE_SCOPE):
        out = jax.lax.pmax(x, axis_name)
    _tally("exchange_all_reduces", 1)
    return out


# logical activation axis -> mesh axes (None = replicated)
ACTIVATION_RULES = {
    "batch": ("pod", "data"),
    "seq": None,            # overridden to "model" for SP in long-context cells
    "kv_seq": "model",      # sequence-parallel KV cache
    "heads": "model",
    "embed": None,
    "mlp": "model",
    "expert": "model",
    "vocab": "model",
}


def _active():
    return getattr(_state, "ctx", None)


@contextlib.contextmanager
def mesh_context(mesh: Mesh, rules: Optional[dict] = None):
    """Activate sharding constraints for model code traced inside."""
    prev = _active()
    merged = dict(ACTIVATION_RULES)
    if rules:
        merged.update(rules)
    # drop axes the mesh doesn't have (e.g. single-pod mesh has no "pod")
    def _filter(v):
        if v is None:
            return None
        axes = (v,) if isinstance(v, str) else tuple(v)
        kept = tuple(a for a in axes if a in mesh.axis_names)
        return kept if kept else None
    merged = {k: _filter(v) for k, v in merged.items()}
    _state.ctx = (mesh, merged)
    try:
        yield
    finally:
        _state.ctx = prev


def logical_sharding_constraint(x, logical_axes):
    """with_sharding_constraint against the active mesh; no-op otherwise."""
    ctx = _active()
    if ctx is None:
        return x
    mesh, rules = ctx
    spec = []
    for name in logical_axes:
        if name is None:
            spec.append(None)
        else:
            spec.append(rules.get(name))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*spec)))


def batch_axes(mesh: Mesh):
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _divisible(n: int, mesh: Mesh, axis) -> bool:
    if axis is None:
        return False
    axes = (axis,) if isinstance(axis, str) else axis
    size = 1
    for a in axes:
        if a not in mesh.axis_names:
            return False
        size *= mesh.shape[a]
    return n % size == 0 and n >= size


# (regex on param path, callable(shape, mesh) -> PartitionSpec entries for the
#  *unstacked* param; a leading scan/stack dim gets None prepended by caller)
def param_partition_spec(path: str, shape: tuple, mesh: Mesh,
                         stacked: bool = False) -> P:
    """Parameter partitioning: TP over 'model', FSDP over 'data'.

    Falls back to replication on any non-divisible dim (correctness first —
    the dry-run roofline shows the cost of every such fallback).
    """
    core = shape[1:] if stacked else shape
    spec = _param_spec_core(path, core, mesh)
    if stacked:
        spec = (None,) + tuple(spec)
    return P(*spec)


def _d(n, mesh, axis):
    return axis if _divisible(n, mesh, axis) else None


def _param_spec_core(path: str, shape: tuple, mesh: Mesh):
    m = mesh
    if re.search(r"(embed|lm_head)", path):
        # (vocab, d) — vocab over model, d over data (FSDP)
        return (_d(shape[0], m, "model"), _d(shape[1], m, "data"))
    if re.search(r"\bwq\b", path):         # (d, H, hd): heads over model, else
        # replicated (sharding head_dim would make attention contractions
        # partial-sum and explode collectives)
        return (_d(shape[0], m, "data"), _d(shape[1], m, "model"), None)
    if re.search(r"\bw[kv]\b", path):      # (d, KV, hd) — KV may be tiny
        return (_d(shape[0], m, "data"), _d(shape[1], m, "model"), None)
    if re.search(r"\bwo\b", path) and len(shape) == 3:  # (H, hd, d)
        return (_d(shape[0], m, "model"), None, _d(shape[2], m, "data"))
    if re.search(r"router", path):         # (d, E)
        return (None, _d(shape[1], m, "model"))
    if re.search(r"(moe|expert)", path) and len(shape) == 3:  # (E, d, f)
        return (_d(shape[0], m, "model"), _d(shape[1], m, "data"), None)
    if re.search(r"\bwi\b|\bwg\b", path) and len(shape) == 2:  # (d, f)
        return (_d(shape[0], m, "data"), _d(shape[1], m, "model"))
    if re.search(r"\bwo\b", path) and len(shape) == 2:         # (f, d)
        return (_d(shape[0], m, "model"), _d(shape[1], m, "data"))
    if re.search(r"in_proj|out_proj", path) and len(shape) == 2:
        return (_d(shape[0], m, "data"), _d(shape[1], m, "model")) \
            if "in_proj" in path else (_d(shape[0], m, "model"), _d(shape[1], m, "data"))
    if re.search(r"conv_w", path) and len(shape) == 2:         # (w, ch)
        return (None, _d(shape[1], m, "model"))
    # norms, biases, scalars: replicated
    return tuple(None for _ in shape)


def tree_pspecs(params, mesh: Mesh, stacked_prefix: str = "blocks"):
    """PartitionSpec pytree for a parameter tree; leaves under
    ``stacked_prefix`` are treated as scan-stacked (leading n_blocks dim)."""
    from jax.tree_util import tree_map_with_path, keystr

    def one(path, leaf):
        p = keystr(path)
        stacked = stacked_prefix in p
        return param_partition_spec(p, leaf.shape, mesh, stacked=stacked)

    return tree_map_with_path(one, params)


def tree_shardings(params, mesh: Mesh, stacked_prefix: str = "blocks"):
    specs = tree_pspecs(params, mesh, stacked_prefix)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def gather_block_constraint(tree, mesh: Mesh):
    """Per-block ZeRO-3: constrain one scan block's (unstacked) weights to be
    data-replicated — XLA inserts the gather inside the layer loop, bounding
    the gathered working set to one block (jamba-398B can't hold the whole
    gathered tree: 50 GB/device)."""
    from jax.tree_util import keystr, tree_map_with_path

    def one(path, leaf):
        if leaf.ndim < 2:
            return leaf
        spec = _param_spec_core(keystr(path), leaf.shape, mesh)
        spec = tuple(None if ax == "data" or (isinstance(ax, tuple)
                                              and "data" in ax) else ax
                     for ax in spec)
        return jax.lax.with_sharding_constraint(
            leaf, NamedSharding(mesh, P(*spec)))

    return tree_map_with_path(one, tree)


def gathered_shardings(params, mesh: Mesh, stacked_prefix: str = "blocks"):
    """ZeRO-3 forward shardings: the FSDP ('data') axis dropped, TP ('model')
    kept.  Constraining the per-step bf16 weight copy to these makes XLA
    all-gather each weight ONCE per step (hoisted out of the microbatch scan)
    instead of all-reducing every activation that contracts a data-sharded
    weight dim — see EXPERIMENTS.md §Perf iteration A2."""
    specs = tree_pspecs(params, mesh, stacked_prefix)

    def drop_data(s):
        return P(*(None if ax == "data" or (isinstance(ax, tuple)
                                            and "data" in ax) else ax
                   for ax in s))

    return jax.tree.map(lambda s: NamedSharding(mesh, drop_data(s)), specs,
                        is_leaf=lambda x: isinstance(x, P))
