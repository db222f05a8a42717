"""Mean over the window's calls of the time the program's ``cohm.prep``
span was open inside the call: an entry point's host preparation before
it calls its compiled program (schedule stacking, initial tables, default
keys, chunk keys)."""

import programtrace


def read(run):
    trace = programtrace.read(run)
    return None if trace is None else trace.span_ms_per_call("cohm.prep")
