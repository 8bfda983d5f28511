"""The in-tree generators (dbgen / dsdgen ports) and what their drivers
share."""


def scale_factor(text: str) -> float:
    """A scale factor as a number or as TPC writes it: ``5``, ``0.01``,
    ``sf5``, ``SF0.01``."""
    t = text.strip()
    return float(t[2:] if t[:2].lower() == "sf" else t)
