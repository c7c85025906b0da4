"""The peel's share of its memory roofline: the time the chip's HBM needs
to move each answered query's byte floor (``bench/work.py``, from the
real n and m, never the padded bucket), over the peel program's device
time in the trace.  Per query, not per trip, so a peel that does less
work per trip cannot pass 100%.  Until the program names the support
pass inside the peel, the whole peel program stands for the kernel."""

from bench.work import peaks, query_bytes


def read(run):
    p = run.profile
    done = run.answered_in_window()
    if p is None or not p.peel_s or not done:
        return None
    bw = peaks(run.device_kind)["hbm_bytes_per_s"]
    floor_s = sum(query_bytes(run.cell.root, r.query) for r in done) / bw
    return 100.0 * floor_s / p.peel_s
