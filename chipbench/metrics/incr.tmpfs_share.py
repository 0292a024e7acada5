"""Share of the bytes written in the window that landed on the tmpfs
tier: telemetry per-tier ``bytes_written``, tmpfs over all tiers."""


def read(rec):
    c = {k: v for k, v in rec.window.counters.items() if k.startswith("bytes_written.")}
    total = sum(c.values())
    if not total:
        return None
    return 100.0 * c.get("bytes_written.tmpfs", 0) / total
