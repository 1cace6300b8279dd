"""A client-side number of the generators' children."""


def read(facts, field: str, scale: float = 1.0, **_):
    v = facts.get("loadgen", {}).get(field)
    return None if v is None else v * scale
