"""Mean host time from an entry call to its return, before blocking on the
result: the program's host path (argument handling, eager set-up ops, jit
dispatch), from the benchmark's own ``dispatch`` span."""


def read(run):
    d = [(c.dispatched - c.start) * 1e3 for c in run.window.calls]
    return sum(d) / len(d)
