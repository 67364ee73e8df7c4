"""ChaosCarry — the scan runtime's liveness/gap-serving state.

Mirrors the event path's per-site cloud memory under churn: while a site is
dark the cloud keeps answering queries from the freshest reconstruction
that ever arrived (``ReorderCloudNode.serve`` gap-serving).  On device that
memory is an ``{query: (E, k)}`` table carried through the scan — each
step overwrites live rows with the window's fresh estimates and leaves
dead rows untouched, so served tables degrade exactly like the event
cloud's (NaN before a site's first live window, stale afterwards).

The carry rides in ``RuntimeState.chaos`` following the ``adaptive``
None-leaves pattern: ``None`` is an empty pytree subtree, so legacy states
and checkpoints flatten to the same leaves as before the field existed,
and a checkpoint taken mid-outage restores the gap-serving memory
bit-for-bit (tests/test_chaos.py).
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np

from repro.core.types import Array


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ChaosCarry:
    """Per-run chaos carry (membership mask + gap-serving memory)."""

    live: Array          # (E,) bool — membership of the last executed window
    served: dict         # {query: (E, k) f32} freshest served estimate


def make_chaos_carry(n_sites: int, k: int, qnames) -> ChaosCarry:
    # distinct buffers per query (donated-carry runs refuse aliasing);
    # NaN = nothing has ever arrived, matching the event cloud's empty serve
    # (host leaves: the runtime places the carry, like init_state's)
    return ChaosCarry(
        live=np.ones((n_sites,), bool),
        served={q: np.full((n_sites, k), np.nan, np.float32)
                for q in qnames})
