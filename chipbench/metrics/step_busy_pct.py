"""Share of the window's device busy time spent in operations under the
program's ``cohm_step`` name scope (the episode or serving step, Pallas
kernel with its input packing or XLA scan), mean over chips."""

import programtrace


def read(run):
    trace = programtrace.read(run)
    return None if trace is None else trace.scope_busy_pct("cohm_step")
