"""Mean streaming passes over A per SVD job (`info["a_passes"]`)."""
from metrics._common import passes_per_job


def read(run):
    return passes_per_job(run)
