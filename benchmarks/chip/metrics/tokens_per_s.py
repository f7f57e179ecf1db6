"""Output tokens returned inside the window over the window's seconds."""


def read(run):
    w0, w1 = run.window
    n = sum(w0 <= t <= w1 for r in run.requests.values()
            for t in r.token_times)
    return n / (w1 - w0)
