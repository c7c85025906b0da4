"""Chips of the slot mesh that a batch's members occupy, mean over the
batches packed in the traced window: the ``chips_used`` on the program's
``pack`` span (``repro.api.planner``; the number its ``batch_chips_used``
histogram also observes).  A chip whose slots are all empty still runs
every trip of the loop.  A program whose spans carry no such count has
nothing to read."""


def read(run):
    used = [
        ev["args"]["chips_used"] for ev in run.spans
        if ev["name"] == "pack" and "chips_used" in ev.get("args", {})
    ]
    return sum(used) / len(used) if used else None
