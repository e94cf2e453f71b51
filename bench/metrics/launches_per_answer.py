"""Device programs the step loop launched (``StepStats.launches``) per
answer, over the window."""


def read(win):
    return win.launches / win.answers
