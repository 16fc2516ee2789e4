"""Pallas kernels of the TASTI hot path: distance_topk (index build and
crack), fpf_update (representative selection) and propagate (serving).

Each kernel package holds ``kernel.py`` (the Pallas kernel), ``ops.py`` (the
jitted entry point that pads and picks an implementation) and ``ref.py``
(the pure-jnp reference the parity tests compare against).
"""


def resolve_impl(impl: str) -> str:
    """``"auto"`` -> the compiled Pallas kernel on a TPU, else the XLA
    reference.  Interpret mode is a test tool, never an execution path."""
    if impl != "auto":
        return impl
    import jax
    return "pallas" if jax.devices()[0].platform == "tpu" else "xla"
