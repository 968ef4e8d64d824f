"""Set-up time: from the start of the process to the window's opening
(weights, engine, warm-up of every program, the lead-in)."""


def read(run):
    return run.setup_s
