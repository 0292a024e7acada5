"""Milliseconds per block-iteration in ``np.save`` and ``fsync`` under
``SeaMount`` (SeaFS write and placement): the ``incr.write`` spans' mean."""


def read(rec):
    return rec.mean_ms("incr.write")
