"""Device idle time inside the host stages of a refresh block (the
program's ``repro.refresh.keys``, ``.dispatch``, ``.pull`` and ``.commit``
annotations; ``.wait`` is left out, the device being busy then), per
traced block (``repro.refresh`` annotations), in milliseconds."""
UNIT = "ms"
LAYER = "resident and pool"
MOVES = "transitions_per_s"
TRACED = True
HOST_STAGES = ("repro.refresh.keys", "repro.refresh.dispatch",
               "repro.refresh.pull", "repro.refresh.commit")
SPANS = ("repro.refresh",) + HOST_STAGES


def read(rec):
    t = rec.get("trace", {})
    blocks = t.get("span_count", {}).get("repro.refresh")
    idle = t.get("idle_in_span_s", {})
    if not blocks or any(s not in idle for s in HOST_STAGES):
        return None
    return 1e3 * sum(idle[s] for s in HOST_STAGES) / blocks
