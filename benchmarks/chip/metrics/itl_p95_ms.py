"""95th percentile of every gap between consecutive output tokens of the
window's requests, in ms."""

from chipbench.harness import percentile


def read(run):
    gaps = [1e3 * (b - a) for r in run.window_requests()
            for a, b in zip(r.token_times, r.token_times[1:])]
    return percentile(gaps, 95)
