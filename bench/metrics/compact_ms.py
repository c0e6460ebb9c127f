"""Device milliseconds per completed request of the traced window's ops
whose innermost operator scope is ``compact``:
compaction (valid rows packed into a smaller capacity)."""
import scopes


def read(rec):
    return scopes.ms_per_query(rec, "compact")
