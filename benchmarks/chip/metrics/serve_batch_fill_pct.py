"""serve_batch_fill_pct: station-channel series served over the bucket slots
dispatched for them (served plus padded), from the server's own counters
over the window."""


def read(rc):
    c = rc.counts
    total = c.get("series_served", 0.0) + c.get("padded_series", 0.0)
    if total <= 0:
        return None
    return 100.0 * c["series_served"] / total
