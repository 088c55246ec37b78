"""Accelerator selection.

Analog of the reference's ``accelerator/real_accelerator.py:37,55``
(``get_accelerator``/``set_accelerator``): the accelerator IS the JAX
backend — ``tpu``, or ``cpu`` for the virtual test mesh
(``JAX_PLATFORMS=cpu``).  ``set_accelerator()`` injects another.
"""

_accelerator = None


def _detect_platform():
    """``jax.default_backend()``; a backend that fails to initialise
    raises here (never a quiet CPU run), and so does one this framework
    has no kernels for."""
    import jax
    platform = jax.default_backend()
    if platform not in ("tpu", "cpu"):
        raise RuntimeError(
            f"unsupported JAX backend {platform!r}: deepspeed_tpu runs on "
            f"'tpu', or on 'cpu' (JAX_PLATFORMS=cpu) for tests")
    return platform


def get_accelerator():
    global _accelerator
    if _accelerator is None:
        from .tpu_accelerator import TPU_Accelerator, CPU_Accelerator
        if _detect_platform() == "cpu":
            _accelerator = CPU_Accelerator()
        else:
            _accelerator = TPU_Accelerator()
    return _accelerator


def set_accelerator(accel):
    global _accelerator
    _accelerator = accel
    return _accelerator
