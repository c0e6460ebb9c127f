"""Device milliseconds per completed request of the traced window's ops
whose innermost operator scope is ``aggregate``:
the group-by (the mean rating per user: segment sums and the mapping of
ids to groups)."""
import scopes


def read(rec):
    return scopes.ms_per_query(rec, "aggregate")
