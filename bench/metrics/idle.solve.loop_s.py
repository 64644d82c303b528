"""Device-idle seconds per solve job whose innermost program span is
`solve.loop` or a span nested in it: the solver's eager seed pass and
the building, lowering and dispatch of its while_loop, which the
program does again on every call."""
from metrics._spans import idle_per_job


def read(run):
    return idle_per_job(run, lambda path: "solve.loop" in path)
