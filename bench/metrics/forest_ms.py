"""Device milliseconds per query of the decision-forest kernel: the trace's
ops whose name starts with "forest" (the kernel's custom call is named
after its entry, ``forest_predict``), over the queries of the traced
window."""


def read(rec):
    tr = rec["trace"]
    if tr is None or not rec["completed"]:
        return None
    s = sum(v for k, v in tr["ops"].items() if k.startswith("forest"))
    return s / rec["completed"] * 1e3 if s > 0 else None
