"""Pallas kernels for the scheduler's compute hot-spots (``maxplus``,
``flash_attention``), each with a pure-jnp oracle in its ``ref.py``."""
from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None) -> bool:
    """Whether a Pallas call runs in interpret mode — one rule for every
    kernel wrapper, decided from the backend that is present.

    ``None`` (what every caller passes) compiles on a TPU backend and
    interprets elsewhere: the CPU backend has no Mosaic lowering, and tests
    run there.  ``False`` compiles for the TPU regardless, which is what an
    ahead-of-time compile for a described chip needs on a CPU host.
    ``True`` on a TPU backend raises: interpreting there would silently
    time the interpreter instead of the device.
    """
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        return not on_tpu
    if interpret and on_tpu:
        raise ValueError("Pallas interpret mode on a TPU backend would hide "
                         "the device; pass interpret=None")
    return bool(interpret)
