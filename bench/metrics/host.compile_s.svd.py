"""Seconds per job of JAX's compile-duration events
(`/jax/core/compile/*`: tracing, lowering, backend compile) inside the
measured window: the host dispatch work each job pays again because the
program builds its loops anew on every call."""
from metrics._common import compile_per_job


def read(run):
    return compile_per_job(run)
