"""setup_s: seconds from process start to the first measured request:
the fleet's weights, the store build (Alg. 1) and commit, the reopen,
compiles and warm-up (host clock)."""


def read(run):
    return run.setup_s
