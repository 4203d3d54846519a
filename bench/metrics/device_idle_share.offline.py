"""Device idle share of the traced window, in %: 1 - busy / window,
busy being the union of op intervals, averaged over the cell's chips."""


def read(record: dict):
    trace = record.get("trace")
    if not trace or not trace["window_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
