"""Milliseconds per block-iteration in ``np.load`` under ``SeaMount``
(interception and the SeaFS read): the ``incr.read`` spans' mean."""


def read(rec):
    return rec.mean_ms("incr.read")
