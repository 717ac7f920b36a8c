"""Device time per encoded frame: the union of the device-operation intervals
in the traced span over the frames submitted in it."""


def read(run):
    tr = run["trace"]
    if not tr or not tr["frames"] or not tr["busy_s"]:
        return None
    return tr["busy_s"] * 1e3 / tr["frames"]
