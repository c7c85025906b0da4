"""Device time of the peel program's executions in the profiler trace, per
while-loop trip, over the batches whose peel ran inside the traced window.

A batch's trips are the ``trips`` on its ``unpack`` span, and the trace's
``jit_peel`` time is clipped to the window.  Two batches run across the
window's edges: one read back inside the window (its ``unpack`` there, its
``device-wait`` before the open) and one still running at the close (its
``device-wait`` there, its ``unpack`` after).  Their trips are left out, and
their device time inside the window, taken on the host's clock (the open to
the first one's readback, the second one's wait to the close), comes off the
trace's time, so that time and trips cover the same batches.  A program
whose spans carry no trip count has nothing to read."""


def read(run):
    p = run.profile
    waits, unpacks = {}, {}
    for ev in run.spans:
        args = ev.get("args", {})
        if ev["name"] == "device-wait":
            waits[args.get("batch")] = ev
        elif ev["name"] == "unpack" and "trips" in args:
            unpacks[args.get("batch")] = ev
    if p is None or not p.peel_s or not unpacks:
        return None
    close_us = 1e6 * run.window_close
    trips = sum(ev["args"]["trips"] for batch, ev in unpacks.items() if batch in waits)
    edge_us = sum(ev["ts"] - 1e6 * run.window_start
                  for batch, ev in unpacks.items() if batch not in waits)
    edge_us += sum(close_us - ev["ts"] for batch, ev in waits.items()
                   if batch not in unpacks and ev["ts"] + ev["dur"] > close_us)
    if not trips:
        return None
    return 1e3 * (p.peel_s - p.devices * edge_us / 1e6) / trips
