"""Answers over all the time of the window (host clock)."""


def read(win):
    return win.answers / win.seconds
