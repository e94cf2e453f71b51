"""Process start to window start (host clock): weights, engine,
warm-up and every compile or cache load."""


def read(win):
    return win.setup_s
