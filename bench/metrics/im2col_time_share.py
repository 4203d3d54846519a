"""The plan's activation unfolding over device busy time, in % (traced run).

Device time of the plan's ops under the ``im2col`` named scope (the conv
kernels' patch and tap building and its layout copies; ``im2col_s`` of
``bench/trace_scopes.py``) over the union of all op intervals.  None where
no op carries a segment scope, as for a program built without them.
"""
from bench import trace_scopes


def read(record: dict):
    r = trace_scopes.for_record(record)
    if r is None or r["im2col_s"] is None or not r["busy_s"]:
        return None
    return 100.0 * r["im2col_s"] / r["busy_s"]
