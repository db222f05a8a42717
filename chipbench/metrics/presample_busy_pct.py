"""Share of the window's device busy time spent in operations under the
program's ``cohm_presample`` name scope (select noise, decay schedule,
fault rows, serving arrivals), mean over chips."""

import programtrace


def read(run):
    trace = programtrace.read(run)
    return None if trace is None else trace.scope_busy_pct("cohm_presample")
