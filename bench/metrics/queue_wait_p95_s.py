"""``queue_wait_p95_s``: 95th percentile of ``ServerStats.queue_wait``
(submission to slot placement, on the scheduler's clock) over the samples
recorded inside the window."""


def read(art):
    w = art["win"]
    if not w.get("queue_wait_n"):
        return None
    return w["queue_wait_p95_s"]
