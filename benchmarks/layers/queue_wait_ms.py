"""Batcher: self time of admission-wait and batcher-queue-wait per answered
query.
Stages and families: ``stages.py``."""

import stages


def read(ctx):
    return stages.self_ms(ctx, "queue_wait_ms")
