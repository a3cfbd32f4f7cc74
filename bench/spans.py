"""Sums over the program's ``repro.obs`` span events of a window."""


def total_s(spans: list, name: str) -> float:
    return sum(e["dur"] for e in spans if e["name"] == name)


def count(spans: list, name: str) -> int:
    return sum(1 for e in spans if e["name"] == name)
