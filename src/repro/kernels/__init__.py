"""Pallas TPU kernels, each with a jitted ``ops`` wrapper and a jnp ``ref``."""
import jax


def interpret_mode() -> bool:
    """Run Pallas in interpret mode on the CPU platform (tests, CI) and
    nowhere else: on a TPU every kernel compiles to Mosaic."""
    return jax.default_backend() == "cpu"
