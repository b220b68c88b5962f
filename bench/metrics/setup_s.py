"""Set-up time: start of the run to the end of the warm-up job (loading,
compiling or reading the compile cache, the first plan)."""


def read(run):
    return run.setup_s
