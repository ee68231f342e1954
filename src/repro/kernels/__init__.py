"""Pallas TPU kernels of the output layer and the fused training loss.

``on_tpu`` is the one place that picks a path from the platform: on a TPU
the serving engine runs the compiled kernels; elsewhere it runs their XLA
reference bodies, and a kernel called directly runs in interpret mode
(how the CPU tests pin each kernel to its reference)."""
import jax


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"
