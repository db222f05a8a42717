"""The episode program's share of its roofline: the least time the window's
invocations need at the chip's published peaks (``work.per_invocation``,
the larger of bytes over HBM bandwidth and operations over peak rate),
over the device busy time of the window's programs, averaged over chips.
It reads the same work whatever lowering runs the step."""

import work


def read(run):
    red = run.reduction
    if red is None or not red["busy_s"]:
        return None
    by, op = run.driver.work()
    calls = red["n_calls"]
    least, bound = work.roofline_seconds(by * calls, op * calls, run.peaks)
    busy = sum(red["busy_s"].values())
    if busy <= 0.0:
        return None
    run.notes["soc_step_roofline_bound"] = bound
    return 100.0 * least / busy
