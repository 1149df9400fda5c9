# OPTIONAL layer. Add <name>.py (or .cu) + ops.py + ref.py ONLY
# for compute hot-spots the paper itself optimizes with a custom
# kernel. Leave this package empty if the paper has none.
"""Shared kernel-backend selection.

Pallas kernels lower natively on TPU and fall back to the Pallas
interpreter everywhere else (CPU CI, local dev).  Every ops.py wrapper
resolves its ``interpret`` flag through :func:`resolve_interpret` so the
decision is made in exactly one place:

  * explicit ``interpret=True/False`` at the call site always wins;
  * else the ``REPRO_KERNELS_INTERPRET`` env var (``1``/``true`` forces
    the interpreter, ``0``/``false`` forces native lowering);
  * else autodetect: native iff the default JAX backend is TPU.
"""
from __future__ import annotations

import os
from typing import Optional

_ENV = "REPRO_KERNELS_INTERPRET"


def backend_is_tpu() -> bool:
    """True when JAX's default backend is a TPU.  A backend that fails
    to initialize raises here: falling back to the interpreter would
    hide a missing device behind a slow stand-in."""
    import jax
    return jax.default_backend() == "tpu"


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """Resolve an ops-level ``interpret`` argument to a concrete bool."""
    if interpret is not None:
        return bool(interpret)
    env = os.environ.get(_ENV)
    if env is not None and env.strip() != "":
        return env.strip().lower() not in ("0", "false", "no")
    return not backend_is_tpu()


def kernels_native_default() -> bool:
    """Serving-default kernel wiring: True when the resolved backend
    lowers Pallas natively (real TPU, or the env var forcing native) —
    serving entry points then flip ``attention.use_kernels(True)`` so
    the paged decode/prefill kernels dereference block tables at DMA
    time instead of materializing the jnp gather view."""
    return not resolve_interpret(None)
