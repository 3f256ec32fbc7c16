"""Share of the routing decisions that chose an expert held on this chip:
the window delta of the service's ``moe.routed_local`` ((lane, expert)
pairs routed to the held experts, summed over layers, as the decode
program counts them) over the decisions the program made, every lane of
every model step times ``num_experts_per_tok`` times the layers. With
uniform routing it is held / router outputs (9 / 72 = 0.125). None where
the program counts no such pairs."""


def read(rec):
    c = (rec.get("registry") or {}).get("counters", {})
    lanes = rec["counters"]["lane_steps"]
    if "moe.routed_local" not in c or not lanes:
        return None
    m = rec["model"]
    return c["moe.routed_local"] / (
        lanes * m["num_experts_per_tok"] * m["num_hidden_layers"])
