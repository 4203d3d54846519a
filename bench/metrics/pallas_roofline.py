"""Pallas kernels' share of their roofline, in % (traced run).

Sum of least times over sum of device times of the Pallas calls in the
traced window.  A call's least time is max(ops / int8 peak, bytes / HBM
bandwidth): ops are 2*M*K*N of the matmul-family calls from their operand
shapes (packed int4 counted unpacked), bytes the operand and result bytes
the compiled HLO declares (``bench/trace_reduce.py`` joins them).
"""


def read(record: dict):
    trace = record.get("trace")
    calls = (trace or {}).get("pallas_calls")
    if not calls:
        return None
    peaks = record["peaks"]
    least = sum(max(c["ops"] / peaks["int8_ops_per_s"],
                    c["bytes"] / peaks["hbm_bytes_per_s"]) for c in calls)
    spent = sum(c["s"] for c in calls)
    return 100.0 * least / spent if spent else None
