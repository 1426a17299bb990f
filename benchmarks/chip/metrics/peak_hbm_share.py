"""Peak device memory in use over the device's limit (%)."""


def read(run):
    m = run.memory
    if not m or not m.get("bytes_limit"):
        return None
    return 100.0 * m["peak_bytes_in_use"] / m["bytes_limit"]
