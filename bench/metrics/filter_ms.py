"""Device milliseconds per completed request of the traced window's ops
whose innermost operator scope is ``filter``:
filters (where an ML predicate, such as an interest DNN over pairs, runs)."""
import scopes


def read(rec):
    return scopes.ms_per_query(rec, "filter")
