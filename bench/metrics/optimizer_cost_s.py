"""Host seconds inside the optimizer's ``optimizer.cost`` spans: costing
candidate plans during the search, which the program totals over the
process (``repro.tracing.published_s``; a run searches once, in its
set-up)."""


def read(rec):
    try:
        from repro import tracing
    except ImportError:
        return None
    return tracing.published_s.get("optimizer.cost")
