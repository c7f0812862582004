"""Process start to window start: loading, compiling or loading compiled
programs, building the cell's inputs and warming up (host clock)."""


def read(ctx):
    return ctx["setup_s"]
