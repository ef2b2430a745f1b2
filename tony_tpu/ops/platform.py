"""Single source of truth for "are pallas kernels compiled here?".

Pallas kernels lower through Mosaic on a TPU backend; everywhere else
they run in interpret mode. Keeping the check in one place stops one
kernel module from deciding differently from the rest and silently
running the interpreter on the chip. A backend that fails to start
raises here: "no device" must never read as "not a TPU".
"""

from __future__ import annotations

import jax


def on_tpu() -> bool:
    """True when the default backend compiles pallas via Mosaic."""
    return jax.devices()[0].platform == "tpu"


def interpret_mode() -> bool:
    """Value for ``pallas_call(interpret=...)`` on this backend."""
    return not on_tpu()
