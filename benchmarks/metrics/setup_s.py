"""Process start to the window's opening: tables, service start, prewarm,
warm-up passes and all compilation."""


def read(run):
    return run.setup_s
