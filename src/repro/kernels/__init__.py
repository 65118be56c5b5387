"""Pallas TPU kernels (validated with interpret=True on CPU) + jnp oracles.

``default_interpret()`` is the single resolver for the kernels' execution
posture: every kernel entry point takes ``interpret=None`` and resolves it
here, so compiled execution on a real TPU backend does not require threading
``interpret=False`` through every call site.
"""
from __future__ import annotations

import os

_TRUTHY = ("1", "true", "yes", "on")
_FALSY = ("0", "false", "no", "off")


def default_interpret() -> bool:
    """Process-default for the kernels' ``interpret`` flag.

    Precedence:

    1. ``REPRO_INTERPRET`` env var, when set ("1"/"true"/... forces interpret
       mode, "0"/"false"/... forces compiled Mosaic lowering);
    2. otherwise auto: ``False`` on a real TPU backend (compiled execution),
       ``True`` everywhere else (CPU/GPU, where the Pallas TPU kernels only
       run under the Python interpreter).

    Resolution happens at trace time: a jitted call that already traced with
    one posture does not re-read the env var.
    """
    env = os.environ.get("REPRO_INTERPRET")
    if env is not None:
        v = env.strip().lower()
        if v in _TRUTHY:
            return True
        if v in _FALSY:
            return False
        raise ValueError(
            f"REPRO_INTERPRET={env!r}: expected one of {_TRUTHY + _FALSY}")
    import jax

    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: bool | None) -> bool:
    """``None`` → :func:`default_interpret`; an explicit bool passes through."""
    return default_interpret() if interpret is None else bool(interpret)


def require_compiled(interpret: bool | None = None) -> None:
    """Refuse to run anywhere but compiled on a TPU.

    For runs whose results are only meaningful on the chip (``chip_smoke.py``,
    measurements): raises ``RuntimeError`` when JAX's backend is not a TPU,
    and when ``interpret`` — a config's ``interpret`` field, resolved through
    :func:`resolve_interpret`, so ``$REPRO_INTERPRET`` counts too — would run
    the Pallas kernels in the interpreter there."""
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        raise RuntimeError(
            f"compiled run needs a TPU backend; JAX found {backend!r}")
    if resolve_interpret(interpret):
        source = ("interpret=True" if interpret
                  else f"REPRO_INTERPRET={os.environ.get('REPRO_INTERPRET')}")
        raise RuntimeError(
            f"{source} would run the Pallas kernels in interpret mode on the "
            "TPU; unset it for a compiled run")
