"""Which way a Pallas kernel runs on the current JAX backend.

The kernels in this package are Mosaic (TPU) kernels. On ``tpu`` they
compile; on ``cpu`` — the test suite — they run in Pallas interpret
mode. Any other backend is an error: a plug-in platform that is not
named ``tpu`` must never interpret a kernel on the device and report
the result as a kernel run.
"""

from __future__ import annotations

import jax


class PallasBackendError(RuntimeError):
    """A Pallas kernel was asked to run where it can neither compile
    nor legitimately interpret."""


def resolve_interpret(interpret: bool | None = None) -> bool:
    """The ``interpret=`` value for a ``pallas_call`` on this backend.

    ``None`` picks by backend (False on tpu, True on cpu). An explicit
    ``True`` is honoured on cpu only.
    """
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise PallasBackendError(
            f"Pallas kernels compile on 'tpu' and interpret on 'cpu' "
            f"only; the JAX backend is {backend!r}. Disable the "
            f"TPU_USE_PALLAS_* flags on this platform.")
    if interpret is None:
        return backend == "cpu"
    if interpret and backend != "cpu":
        raise PallasBackendError(
            f"interpret=True is a CPU test mode; refusing to interpret "
            f"a Pallas kernel on backend {backend!r}")
    return interpret
