"""Device milliseconds per completed request of the traced window's ops
whose innermost operator scope is ``cross_join``:
the cross join (users x kept movies, materialized)."""
import scopes


def read(rec):
    return scopes.ms_per_query(rec, "cross_join")
