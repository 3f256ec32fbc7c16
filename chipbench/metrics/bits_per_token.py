"""Container bytes x 8 over input tokens, over every job the run finished
(client side: the bytes the user stores; for a read-back, the bytes of
the containers it read)."""


def read(rec):
    done = [j for j in rec["jobs"] if "bytes" in j]
    if not done:
        return None
    return 8.0 * sum(j["bytes"] for j in done) / sum(j["n"] for j in done)
