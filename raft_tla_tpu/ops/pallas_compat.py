"""Platform probe + execution-mode switch for the Pallas fingerprint
kernel (ops/pallas_fp.py): compile for Mosaic when a TPU backend is
present, run the kernel under the Pallas interpreter when the caller is
testing on CPU, and otherwise fall back to the bit-identical jnp twin
rather than paying interpreter overhead in production paths.

Modes (returned by :func:`resolve`):

- ``MOSAIC``    — real ``pl.pallas_call`` compile; requires a TPU.
- ``INTERPRET`` — ``pallas_call(interpret=True)``: the kernel body runs
  as ordinary traced JAX under the grid emulator.  Bit-identical to the
  Mosaic build by Pallas's contract; this is how every CPU parity test
  executes the kernel.
- ``JNP``       — skip Pallas entirely and use the portable jnp twin
  (``ops.fingerprint.fingerprint``).
"""

from __future__ import annotations

import jax

MOSAIC = "mosaic"
INTERPRET = "interpret"
JNP = "jnp"


def on_tpu() -> bool:
    """True when the default JAX backend is a TPU."""
    return jax.default_backend() == "tpu"


def resolve(interpret: bool | None) -> str:
    """Pick the execution mode for a Pallas kernel call.

    ``interpret=True`` forces the interpreter (CPU tests assert parity
    through this path); ``interpret=False`` forces a real Mosaic build
    (loud failure off-TPU beats silently testing nothing); ``None``
    means auto: Mosaic on TPU, otherwise the jnp twin.
    """
    if interpret:
        return INTERPRET
    if on_tpu():
        return MOSAIC
    if interpret is None:
        return JNP
    return MOSAIC                    # interpret=False off-TPU: fail loudly
