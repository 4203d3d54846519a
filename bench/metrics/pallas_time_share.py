"""Pallas kernels' device time over device busy time, in % (traced run)."""


def read(record: dict):
    trace = record.get("trace")
    if not trace or not trace["busy_s"] or not trace["pallas_calls"]:
        return None
    return 100.0 * trace["pallas_s"] / trace["busy_s"]
