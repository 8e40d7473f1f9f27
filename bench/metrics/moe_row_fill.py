"""moe_row_fill: the held experts' live rows over the rows of the slots
the grouped kernels executed (each expert's rows padded to whole 128-row
slots), over the window's calls, in percent; from the program's per-call
counts (``bench/work_moe.py``)."""


def read(rec):
    work = rec.get("work")
    if not hasattr(work, "row_fill") or not rec["calls"]:
        return None
    return work.row_fill(rec["calls"])
