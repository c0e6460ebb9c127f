"""Share of its roofline the decision-forest kernel reaches: the least time
its algorithmic work needs on this chip (node tests at the bf16 peak, as
the VPU's compare rate is not published, or bytes at the HBM rate,
whichever is longer) over its device time per query (the trace's ops
whose name starts with "forest", as for ``forest_ms``)."""


def read(rec):
    tr, work, peaks = rec["trace"], rec.get("work"), rec["peaks"]
    if tr is None or not work or "forest_ops" not in work:
        return None
    s = sum(v for k, v in tr["ops"].items() if k.startswith("forest"))
    if s <= 0 or not rec["completed"]:
        return None
    least = max(work["forest_ops"] / peaks["bf16_flops_per_s"],
                work["forest_bytes"] / peaks["hbm_bytes_per_s"])
    return least / (s / rec["completed"]) * 100
