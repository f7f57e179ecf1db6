"""90th percentile, over the window's requests, of the time from a
request's send time (when its client sent it) to its
first token, in ms."""

from chipbench.harness import percentile


def read(run):
    return percentile([1e3 * (r.token_times[0] - r.sent)
                       for r in run.window_requests() if r.token_times], 90)
