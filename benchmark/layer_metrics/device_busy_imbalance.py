"""Layer: search_mesh. Busiest device's busy seconds over the least busy
device's, in the traced window. 1 is an even spread of the candidates."""


def read(obs):
    trace = obs.get("trace")
    if not trace or len(trace["devices"]) < 2:
        return None
    busy = [d["busy_s"] for d in trace["devices"]]
    return max(busy) / min(busy) if min(busy) > 0 else None
