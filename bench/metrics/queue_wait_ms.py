"""Mean time a request of the window spent queued, submit to dispatch,
from the program's own request timestamps."""


def read(rec):
    waits = rec["queue_wait_s"]
    return sum(waits) / len(waits) * 1e3 if waits else None
