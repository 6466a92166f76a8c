"""1 - union of device-operation intervals / traced window, in percent, in
the replica's process (serving cells)."""


def read(facts):
    tr = facts.get("trace")
    if tr is None or facts["kind"] != "serve":
        return None
    return tr["idle_share_pct"]
