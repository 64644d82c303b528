"""Device-idle seconds per solve job inside `api.solve` and outside
`solve.loop`: the set-up of the composite (`solve.setup`), the planner's
decisions (`planner.plan`) and the api's own host work."""
from metrics._spans import idle_per_job


def read(run):
    return idle_per_job(
        run, lambda path: "api.solve" in path and "solve.loop" not in path)
