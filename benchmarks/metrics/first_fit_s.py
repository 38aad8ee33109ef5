"""Wall of the warm-up fit, with the compile cache as this process found
it: what a job's first fit costs, the eager solve's compiles with it."""


def read(ctx):
    return ctx["first_fit_s"]
