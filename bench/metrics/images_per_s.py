"""images_per_s: images of the requests completed in the window, per second of it."""


def read(run):
    done = sum(r.n for r in run.records if r.status == "done"
               and r.due < run.window_s and r.finished <= run.window_s)
    return done / run.window_s
