"""Device-idle seconds per SVD job whose innermost program span is
`svd.eigh`: the driver's float64 LAPACK eigendecomposition of the Gram on
the host, and the copy of its top-k pairs back to the device."""
from metrics._spans import idle_per_job


def read(run):
    return idle_per_job(run, lambda path: path[-1] == "svd.eigh")
