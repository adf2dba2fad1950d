"""window_compiles (count): XLA compile requests inside the measured
window, as the program's ``repro.analysis.sanitize.compile_budget``
counts them around it. Set-up warms every program the window runs, so
this reads 0."""


def read(run):
    return float(run["compiles"])
