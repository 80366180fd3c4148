"""Gateway edge: from the scraper's last byte sent to the node's
acknowledgement of every row sent (the backlog the edge lets build)."""


def read(ctx):
    a, s = ctx.traffic.acked, ctx.traffic.scrape
    if a is None or s is None or s.t_last_sent is None:
        return None
    return max(0.0, a.t_last_ack - s.t_last_sent) * 1e3
