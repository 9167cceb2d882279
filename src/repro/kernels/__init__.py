"""Pallas TPU kernels. Each kernel has ``kernel.py`` (the ``pallas_call``),
``ops.py`` (the jitted public wrapper) and ``ref.py`` (the pure-jnp oracle
the tests compare it against)."""
from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """Whether a Pallas call runs in interpret mode — the one place the
    kernels' ``interpret`` argument is decided.

    ``None`` means interpret mode on the CPU backend (the kernels have no CPU
    lowering; the tests run them in the interpreter) and the compiled kernel
    on every other backend. Interpret mode is allowed ONLY on the CPU
    backend: asking for it anywhere else raises, so a run on the chip can
    never silently time the interpreter."""
    on_cpu = jax.default_backend() == "cpu"
    if interpret is None:
        return on_cpu
    if interpret and not on_cpu:
        raise ValueError(
            f"Pallas interpret mode is only allowed on the CPU backend, not "
            f"on {jax.default_backend()!r}: on an accelerator the kernel "
            f"must run compiled")
    return bool(interpret)
