"""Share of the traced training window in which no op ran on the device
(1 minus the union of op intervals, averaged over the chips), percent."""


def read(run):
    tr = run["trace"]
    if run["traffic"]["driver"] != "train" or not tr["busy_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
