"""90th percentile, over the window's requests, of the time from a
request's send time to the start of its prefill call, in ms."""

from chipbench.harness import percentile


def read(run):
    return percentile([1e3 * (r.t_prefill - r.sent)
                       for r in run.window_requests() if r.t_prefill], 90)
