"""Share of the traced window in which no operation ran on the device:
100 x (1 - union of the device's operation intervals / window), averaged
over the chips the cell uses."""


def read(run):
    red = run.reduction
    if red is None or not red["busy_s"]:
        return None
    busy = sum(red["busy_s"].values()) / len(red["busy_s"])
    return 100.0 * (1.0 - busy / red["window_s"])
