"""setup_s: seconds from the process's start until the window opens:
imports, weights made from the seed, warm-up (compilation or the compile
cache) and the traffic's own set-up (host clock)."""


def read(run):
    return run.setup_s
