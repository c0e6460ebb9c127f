"""Host seconds of the warm-up: the loop's own steps, run once through so
that the plan cache holds every executable the window uses."""


def read(rec):
    return rec["warmup_s"]
