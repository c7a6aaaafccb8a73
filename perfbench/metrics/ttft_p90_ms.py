"""90th percentile of time to first token, from each request's due time
(requests with no token by the window's close count their wait)."""

from lib.readers import percentile_ms


def read(run):
    return percentile_ms(run["result"]["ttft_s"], 90)
