"""Plain references for the benchmark's correctness checks.

Nothing here imports the system under test: straightforward jnp and
host LAPACK, at a matmul precision the caller names ("highest" for the
reference, "high" for the lower-precision control)."""
