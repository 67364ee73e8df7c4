"""The site mesh: the fleet's site axis over the local devices, its
padding, and the counted collectives of its cross-device exchange.

Both ``shard_map`` paths — the sharded plan engine and the sharded scan
runtime — split the leading E axis on the 1-D ``("sites",)`` mesh of
:func:`site_mesh` and pad it with :func:`site_pad` / :func:`pad_site_axis`.
Only the scan runtime's window step talks across devices: the controller's
water-fill and the drift gate go through :func:`exchange_all_gather` and
:func:`exchange_pmax`, which :func:`count_exchange` counts.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

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

