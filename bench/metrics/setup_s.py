"""Set-up seconds: from the start of bench/run.py to the opening of the
window (cache, registration, history, warm-up, the kernel check)."""


def read(run):
    return run.setup_s
