"""Mean host time of a prefill call in the window, in ms."""


def read(run):
    return run.mean_call_ms("prefill")
