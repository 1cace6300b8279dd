"""The peak of device memory on the fullest chip."""


def read(facts, scale: float = 1e-9, **_):
    v = facts.get("device", {}).get("memory_peak_bytes")
    return None if v is None else v * scale
