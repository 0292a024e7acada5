"""Device-feed stalls per step: the telemetry counter
``device_feed_stalls`` (a step that found no batch on the device yet)
over the window's steps."""


def read(rec):
    steps = rec.window.info.get("steps")
    if not steps:
        return None
    return 100.0 * rec.window.counters["device_feed_stalls"] / steps
