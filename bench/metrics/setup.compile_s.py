"""setup.compile_s: Backend compile seconds during set-up, from jax.monitoring."""

def read(run):
    return run.setup_compile["seconds"]
