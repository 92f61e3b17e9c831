"""Share of the collectives' device time in which no other op ran on the
same device."""


def read(t: dict):
    if t["collective_count"] == 0 or t["collectives_s"] <= 0:
        return None
    return 100.0 * t["collectives_exposed_s"] / t["collectives_s"]
