"""Mean resolve time per batch, ms: the completer's ``serve.resolve`` span
(log append, replies, deadline sweep), as the server's
``BatchRecord.resolve_s`` of the window's batches."""


def read(facts):
    rows = [b for b in facts["batches"] if len(b) > 3]
    if not rows:
        return None
    return 1e3 * sum(b.resolve_s for b in rows) / len(rows)
