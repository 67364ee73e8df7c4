"""Which path a kernel wrapper runs: the compiled Pallas kernel or the jnp
reference.

Every wrapper that takes ``use_kernel=None`` ("auto") resolves it here, so
the stream-statistics pass and the compact-model fit can never disagree
about the device they run on:

  * on a TPU, auto means the compiled Pallas kernel;
  * on any other backend it means the jnp reference, unless the caller
    asked for Pallas interpret mode (the CPU test path for kernel code).

The platform is that of the ``jax.default_device`` in effect, else the
default backend, so work pinned to the host CPU (``with
jax.default_device(jax.devices("cpu")[0])``) on a TPU machine takes the
reference path.  Interpret mode is refused on a TPU: there the kernel
compiles, and a run that asked for the interpreter would silently measure
it instead.
"""
from __future__ import annotations

from typing import Optional

import jax


def kernel_platform() -> str:
    """Platform the next uncommitted computation lands on."""
    dev = jax.config.jax_default_device
    if dev is None:
        return jax.default_backend()
    return dev if isinstance(dev, str) else dev.platform


def resolve_use_kernel(use_kernel: Optional[bool],
                       interpret: bool = False) -> bool:
    """``use_kernel`` (None = auto) -> whether to call the Pallas kernel."""
    on_tpu = kernel_platform() == "tpu"
    if interpret and on_tpu:
        raise ValueError("Pallas interpret mode is a CPU test path; on a "
                         "TPU the kernel compiles (pass interpret=False)")
    if use_kernel is None:
        return on_tpu or bool(interpret)
    return bool(use_kernel)
