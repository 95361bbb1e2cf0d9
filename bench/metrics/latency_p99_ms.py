"""99th percentile of due-to-done latency over every request answered in
the measured window (client layer, host clock). The tail is read here, not
bounded: its runs spread too widely for an end-to-end bound."""
from bench.client import percentile


def read(record):
    lat = record["log"].latency_ms
    return percentile(lat, 99) if lat.size else None
