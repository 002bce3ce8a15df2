"""Images returned inside the window over the window's seconds."""


def read(run):
    return run.items_in_window / run.seconds if run.items_in_window else None
