"""Device milliseconds per completed request of the traced window's ops
whose innermost operator scope is ``join``:
equi-joins (in rec Q2, the trending movies' tag rows gathered by key)."""
import scopes


def read(rec):
    return scopes.ms_per_query(rec, "join")
