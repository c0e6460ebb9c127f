"""Device milliseconds per completed request of the traced window's ops
whose innermost operator scope is ``project``:
projections (where the two towers and the cosine run)."""
import scopes


def read(rec):
    return scopes.ms_per_query(rec, "project")
