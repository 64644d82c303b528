"""Device-idle seconds per SVD job inside `api.svd` and not in
`svd.eigh`: the planner, the Gram's dispatch (`collective.gram`), its
fetch to the host (`svd.fetch`), the dispatch of U = A·VΣ⁻¹
(`svd.recover_u`) and the api's own host work."""
from metrics._spans import idle_per_job


def read(run):
    return idle_per_job(
        run, lambda path: "api.svd" in path and path[-1] != "svd.eigh")
