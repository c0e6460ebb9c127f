"""The query's model operations per second, over the chip's bf16 peak: the
work counts of the logical query times the queries of the traced window,
over its length."""


def read(rec):
    work = rec.get("work")
    if rec["trace"] is None or not work:
        return None
    rate = work["model_flops"] * rec["completed"] / rec["window_s"]
    return rate / (rec["peaks"]["bf16_flops_per_s"] * rec["chips"]) * 100
