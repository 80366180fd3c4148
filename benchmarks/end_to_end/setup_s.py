"""Process start to window open: node start, backfill, flush, tile builds,
warm-up and (in a run that compiles) compilation."""


def read(ctx):
    return ctx.setup_s
