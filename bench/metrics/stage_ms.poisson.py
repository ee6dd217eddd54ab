"""Mean staging time per batch, ms: the stager's ``serve.stage`` span
(stack, pad, host-to-device copy, enqueue of the fused search), as the
server's ``BatchRecord.stage_s`` of the window's batches."""


def read(facts):
    rows = [b for b in facts["batches"] if len(b) > 3]
    if not rows:
        return None
    return 1e3 * sum(b.stage_s for b in rows) / len(rows)
