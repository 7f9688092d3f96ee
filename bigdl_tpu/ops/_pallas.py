"""What every Pallas call site in ``ops/`` shares."""

from __future__ import annotations

from typing import Optional


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """The one place that may choose the Pallas interpreter by itself:
    ``None`` means "interpret on the CPU backend" (the tests' platform,
    the 8-virtual-device mesh included) and nowhere else.  An
    accelerator under any name compiles the Mosaic kernel or fails
    loudly; it is never handed the interpreter in silence.  ``True`` /
    ``False`` are the caller's explicit choice."""
    if interpret is None:
        import jax

        return jax.default_backend() == "cpu"
    return bool(interpret)
