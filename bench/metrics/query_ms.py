"""Milliseconds per query of a one-client closed loop: the whole window
over every query completed in it."""


def read(rec):
    if not rec["completed"]:
        return None
    return rec["window_s"] / rec["completed"] * 1e3
