"""Share of the traced span in which no operation ran on the device:
1 - (union of device busy intervals) / span."""


def read(record):
    tr = record["trace"]
    if tr is None or not tr["devices"]:
        return None
    busy = [d["busy_s"] for d in tr["devices"].values()]
    return 100.0 * (1.0 - sum(busy) / len(busy) / tr["window_s"])
