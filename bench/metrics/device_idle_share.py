"""Share of the traced window in which no operation ran on the device:
1 - the union of device op intervals over the window, in %."""


def read(record):
    trace = record["trace"]
    if not trace or trace["window_s"] <= 0 or not trace["devices"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
