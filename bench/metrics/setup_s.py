"""Process start to the window's start: data, optimizer, warm-up and,
in a run whose compile cache is cold, compilation."""


def read(rec):
    return rec["setup_s"]
