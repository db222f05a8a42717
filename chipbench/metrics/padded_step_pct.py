"""Share of the invocation steps the window's calls ran on padding rows,
counted from the stacked schedules' shapes (lanes padded to the longest)."""


def read(run):
    real = run.driver.invocations_per_call
    pad = run.driver.padded_per_call
    return 100.0 * pad / (real + pad)
