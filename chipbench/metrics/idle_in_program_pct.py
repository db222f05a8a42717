"""Share of the window's device-idle time during which one of the
program's ``cohm.*`` host spans was open: idle time the program's own
host path holds the device, against the benchmark loop, blocking and
pulls.  Mean over chips."""

import programtrace


def read(run):
    trace = programtrace.read(run)
    return None if trace is None else trace.idle_in_program_pct()
