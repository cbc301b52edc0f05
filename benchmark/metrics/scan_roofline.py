import json


def scan_bytes(window, requests):
    """The least bytes the requests' scans have to stream: each scanned
    row's needed columns once.  The column list is the traffic file's;
    the widths are the resident columns' own."""
    total = 0
    for r in requests:
        if r.metrics is None:
            continue
        per_row = sum(
            window.column_bytes[c] for c in window.queries[r.query]["columns"]
        )
        total += r.metrics.rows_scanned * per_row
    return total


def read(window):
    """Least streaming time of the traced requests' scans over the
    device's busy time in the traced window.  Bandwidth-bound by
    construction: a scan does a few operations per byte.  It cannot pass
    100 % while every scanned row's needed columns are read at least
    once."""
    t = window.trace
    if not t or t["busy_s"] <= 0 or not window.peaks:
        return None
    nbytes = scan_bytes(window, window.traced)
    # `busy_s` is the mean over the chips, so the scans stream at all of
    # the chips' bandwidth together
    least_s = nbytes / (window.peaks["hbm_bytes_per_s"] * t["devices"])
    print(json.dumps({
        "phase": "scan_roofline", "scan_bytes": nbytes, "least_s": least_s,
        "busy_s": t["busy_s"], "devices": t["devices"],
        "bound": "HBM bandwidth",
        "program_bytes_scanned": sum(
            r.metrics.bytes_scanned for r in window.traced
            if r.metrics is not None
        ),
    }), flush=True)
    return 100.0 * least_s / t["busy_s"]
