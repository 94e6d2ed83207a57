"""Pallas kernels for the compute hot spots, each with ops (jit'd wrappers)
and ref (pure-jnp oracle) modules."""

from typing import Optional

import jax


def pallas_interpret(interpret: Optional[bool] = None) -> bool:
    """Interpret mode for a Pallas call: the one place that decides it.

    ``None`` (every wrapper's default) compiles natively on a TPU backend and
    interprets everywhere else.  ``False`` compiles natively even here, which
    is what an ahead-of-time compile for a described TPU does.  ``True`` on a
    TPU backend is refused: the interpreter would run on the chip silently and
    report nothing about the kernel."""
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        return not on_tpu
    if interpret and on_tpu:
        raise ValueError("Pallas interpret mode requested on a TPU backend")
    return interpret
