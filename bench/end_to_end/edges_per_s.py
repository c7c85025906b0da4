"""GraphChallenge's rate: the edges of every graph whose query was
answered, over the time from the window's start to its last answer."""


def read(run):
    done = run.answered_in_window()
    elapsed = run.window_close - run.window_start
    if not done or elapsed <= 0:
        return None
    return sum(r.query.graph.m for r in done) / elapsed
