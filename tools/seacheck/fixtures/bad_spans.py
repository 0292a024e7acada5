"""Known-bad: telemetry-drift span violations (rule d, across files).

Linted as if it were ``src/repro/core/telemetry.py``: the SPANS table
registers a span nothing records, and a site records a span the table
does not register.
"""

SPANS = {
    "sea.read": (None, "recorded below"),
    "ghost.span": (None, "registered but never recorded"),
}


def read(telemetry, f):
    with telemetry.span("sea.read"):
        data = f.read()
    telemetry.record_span("stray.span", 0.5)
    return data
