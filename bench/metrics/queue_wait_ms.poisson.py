"""Mean queue wait per request, ms: batch formation minus submit time,
summed over the window's batches (the server's ``BatchRecord.wait_s``) and
divided by their requests."""


def read(facts):
    rows = [b for b in facts["batches"] if len(b) > 3]
    if not rows:
        return None
    return 1e3 * sum(b.wait_s for b in rows) / sum(b.size for b in rows)
