"""Set-up: from process start to the window's start, compiling, loading,
building the state and the first steps included (host clock)."""


def read(run):
    return run.setup_s
