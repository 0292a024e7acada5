"""Milliseconds per block-iteration in ``np.asarray`` of the device
result: the ``incr.d2h`` spans' mean."""


def read(rec):
    return rec.mean_ms("incr.d2h")
