"""Process start to the window's start: imports, weights, engine, compile
(or cache loads), and the steady-state warm traffic."""


def read(run):
    return run.setup_s
