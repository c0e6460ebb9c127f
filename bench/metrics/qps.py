"""Queries completed per second of the window."""


def read(rec):
    return rec["completed"] / rec["window_s"]
