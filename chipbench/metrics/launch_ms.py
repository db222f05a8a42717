"""Mean over the window's calls of the time the program's ``cohm.launch``
span was open inside the call: the jit cache lookup and the call into the
compiled program, until it returns."""

import programtrace


def read(run):
    trace = programtrace.read(run)
    return None if trace is None else trace.span_ms_per_call("cohm.launch")
