"""95th percentile of the program's ``queue`` spans (``repro.api.Session``:
a query's submission to the formation of its batch), over the queries
whose batch was read back in the traced window: the batch id on the
``queue`` span is that of an ``unpack`` span.  A program whose spans
carry no batch id has nothing to read."""

from bench.record import p95


def read(run):
    answered = {
        ev["args"]["batch"] for ev in run.spans
        if ev["name"] == "unpack" and "batch" in ev.get("args", {})
    }
    waits = [
        ev["dur"] / 1e3 for ev in run.spans
        if ev["name"] == "queue" and ev.get("args", {}).get("batch") in answered
    ]
    return p95(waits) if waits else None
