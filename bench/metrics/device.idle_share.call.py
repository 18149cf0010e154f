"""1 - busy / window in the traced window of a closed-loop call cell."""


def read(facts):
    trace = facts.get("trace")
    if not facts.get("call") or not trace:
        return None
    return trace["idle_share"]
