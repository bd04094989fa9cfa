"""How the Pallas kernels run on the current JAX backend.

The one place that decides it: on a TPU every kernel is compiled by
Mosaic; on the CPU (tests and rehearsals) it runs in the Pallas
interpreter. Any other backend is an error, never a silent fallback.
Every kernel entry point takes ``interpret=None`` and resolves it here,
so a caller that wants the interpreter on a TPU has to pass
``interpret=True`` explicitly.
"""
from __future__ import annotations

from typing import Optional

import jax


def interpret_mode(interpret: Optional[bool] = None) -> bool:
    """Resolve a kernel's ``interpret`` flag.

    ``None`` picks by backend: compiled (False) on ``"tpu"``, interpreted
    (True) on ``"cpu"``; raises on any other backend. An explicit bool is
    returned unchanged."""
    if interpret is not None:
        return bool(interpret)
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels run compiled on 'tpu' or interpreted on 'cpu'; "
        f"backend {backend!r} is neither")
