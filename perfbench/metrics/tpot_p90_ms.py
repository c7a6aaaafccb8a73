"""90th percentile over the requests finished in the window of their time
per output token: first token to last, over the tokens after the first."""

from lib.readers import percentile_ms


def read(run):
    return percentile_ms(run["result"]["tpot_s"], 90)
