"""feed_idle_share: the share of the traced window in which the device
was idle while the loop's `loop/feed` span (`host_batch`) was open
(`bench/scopes.py`)."""
from bench import scopes


def read(ctx):
    return scopes.idle_share_under(ctx, scopes.FEED_SPAN)
