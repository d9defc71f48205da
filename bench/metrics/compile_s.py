"""Backend compile seconds during set-up, from ``jax.monitoring``."""


def read(data):
    return data.setup_compile_s
