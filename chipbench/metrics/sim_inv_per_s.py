"""Simulated invocations completed in the window per second of its wall time.

Every real (non-padding) invocation of every completed entry call counts:
training's agent x iteration x row, evaluation's (lane, policy) rows, and
serving's offered requests, admitted or shed.  The time runs from the
window's start to the last call's completion."""


def read(run):
    w = run.window
    return sum(c.invocations for c in w.calls) / w.seconds
