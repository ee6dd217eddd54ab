"""Mean real queries per dispatch over max_batch, from the server's
batch_log rows completed in the window."""


def read(facts):
    sizes = [b[0] for b in facts["batches"]]
    if not sizes:
        return None
    return 100.0 * sum(sizes) / len(sizes) / facts["max_batch"]
