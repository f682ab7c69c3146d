"""Pallas TPU kernels of the feature path, their jnp references (``ref``)
and the one switch that decides how they run on this process's backend."""
from __future__ import annotations

IMPLS = ("auto", "pallas", "xla")


def kernel_impl(impl: str = "auto") -> str:
    """The device-selection switch: how a kernel call site runs here.

    Returns ``"mosaic"`` (the Pallas kernel compiled for the TPU),
    ``"interpret"`` (the same kernel body executed as jax ops — the CPU
    platform's only way to run it) or ``"xla"`` (the jnp reference in
    ``kernels/ref.py``).  ``impl="auto"`` takes the kernel on TPU and the
    reference elsewhere; ``"pallas"`` always takes the kernel; ``"xla"``
    always takes the reference.  So a TPU process runs the reference only
    when a caller asks for it by name.
    """
    import jax

    if impl not in IMPLS:
        raise ValueError(f"unknown kernel impl {impl!r} (expected one of "
                         f"{IMPLS})")
    platform = jax.default_backend()
    if impl == "xla" or (impl == "auto" and platform != "tpu"):
        return "xla"
    if platform == "tpu":
        return "mosaic"
    if platform == "cpu":
        return "interpret"
    raise RuntimeError(f"the Pallas TPU kernels cannot run on {platform!r}; "
                       "use impl='xla'")


def interpret_default() -> bool:
    """``interpret`` for a kernel wrapper whose caller passed none."""
    return kernel_impl("pallas") == "interpret"
