"""Seconds of a stamped stage of set-up."""


def read(facts, stage: str, scale: float = 1.0, **_):
    v = facts.get("stamps", {}).get(stage)
    return None if v is None else v * scale
