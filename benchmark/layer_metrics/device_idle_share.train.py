"""1 - union of device-operation intervals / traced window, in percent,
averaged over the chips (training cells)."""


def read(facts):
    tr = facts.get("trace")
    if tr is None or facts["kind"] != "train":
        return None
    return tr["idle_share_pct"]
