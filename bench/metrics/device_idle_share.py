"""Share of the traced window in which no operation ran on the device,
in percent: 1 - union of busy intervals / window."""


def read(win):
    if win.trace is None or not win.trace["window_s"]:
        return None
    return 100.0 * (1.0 - win.trace["busy_s"] / win.trace["window_s"])
