"""Where the Pallas kernels run: compiled by Mosaic on a TPU, interpreted
everywhere else.

Every kernel entry point takes ``interpret=None`` and resolves it here, so
a caller that leaves the argument out gets the compiled kernel on a TPU and
the interpreter only where no TPU exists.  An explicit ``True`` still forces
the interpreter (tests use it to run a kernel's body on the CPU).
"""

from __future__ import annotations

import jax


def on_tpu() -> bool:
    """True when jax dispatches to a real TPU."""
    return jax.default_backend() == "tpu"


def resolve_interpret(interpret: bool | None) -> bool:
    """``None`` -> interpret only off-TPU; an explicit bool is kept."""
    return (not on_tpu()) if interpret is None else bool(interpret)
