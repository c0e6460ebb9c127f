"""Requests per dispatch over the window, from the server's counters."""


def read(rec):
    return rec["occupancy"]
