"""95th percentile of job latency, from the moment each job was due to
the moment the client saw it done, over every job due in the window; a
job not done by the drain's cap counts at the cap."""
import numpy as np


def read(rec):
    if not rec["jobs"]:
        return None
    lat = [(j.get("t_done", rec["cap_s"]) - j["due"]) for j in rec["jobs"]]
    return 1e3 * float(np.percentile(lat, 95))
