"""Engine step: tokens delivered per pump over the window, from the
engine's own counters (stats(): total_tokens, pumps)."""


def read(facts):
    c = facts.get("counters", {})
    return c["total_tokens"] / c["pumps"] if c.get("pumps") else None
