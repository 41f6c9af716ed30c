"""Mean event-loop inbox dwell (``queue_wait`` sum / count from the
server's ``server-metrics`` scrape, window end minus window start), in ms."""


def read(rec):
    c = rec.counters
    if not c.get("queue_wait_count"):
        return None
    return 1e3 * c["queue_wait_sum_s"] / c["queue_wait_count"]
