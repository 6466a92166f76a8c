"""Summed duration of the collective operations on one device over the
traced window, in percent. Summed, so time hidden under compute counts."""


def read(facts):
    tr = facts.get("trace")
    return None if tr is None else tr["collective_share_pct"]
