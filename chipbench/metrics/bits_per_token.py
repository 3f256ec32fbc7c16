"""Container bytes x 8 over input tokens, over every compress job the run
finished (client side: the bytes the user stores)."""


def read(rec):
    done = [j for j in rec["jobs"] if "blob" in j]
    if not done:
        return None
    return 8.0 * sum(len(j["blob"]) for j in done) / sum(j["n"] for j in done)
