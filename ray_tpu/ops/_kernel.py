"""What every kernel file of ``ops/`` shares: the one decision between a
kernel and its plain ``jnp`` form, and the helpers around a kernel call."""

from __future__ import annotations

import functools
from typing import Optional

import jax


def kernel_mode(interpret: Optional[bool]) -> Optional[bool]:
    """``interpret`` as given; left open, the kernels on a TPU and plain
    ``jnp`` elsewhere.  ``None``: the reference form; ``False``: the
    compiled kernels; ``True``: the kernels through the Pallas
    interpreter."""
    if interpret is None and jax.default_backend() == "tpu":
        return False
    return interpret


def traced_once(*static):
    """``jax.jit`` over a function that builds kernel calls: a model
    calls it once a layer (and again under ``remat``) with the same
    shapes, and each call would trace its kernel bodies afresh, some
    thousand jnp operations of Python a layer.  Under an inner ``jit``
    the first call's jaxpr serves the others and lowers to ONE function
    that every layer calls; XLA inlines it, so the step's program is
    the one it was."""
    return functools.partial(jax.jit, static_argnames=static)


def fit_block(seq: int, block: int) -> int:
    """Largest divisor of ``seq`` that is <= ``block`` (the pallas grids
    need the sequence to divide into whole tiles)."""
    for d in range(min(block, seq), 0, -1):
        if seq % d == 0:
            return d
    return 1


def fold8(x):
    """``[n, lanes]`` summed to ``[8, lanes]``: whole registers added,
    nothing across sublanes."""
    out = x[0:8]
    for r in range(8, x.shape[0], 8):
        out = out + x[r:r + 8]
    return out
