"""99th percentile of how late the client submitted each request after it
was due (client layer, host clock)."""
from bench.client import percentile


def read(record):
    lag = record["log"].gen_lag_ms
    return percentile(lag, 99) if lag.size else None
