"""Mean host time of a decode call in the window, in ms."""


def read(run):
    return run.mean_call_ms("decode")
