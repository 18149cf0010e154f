"""Bytes the chosen plan moves by the predictor's own count (the sum of
``Impl.traffic_bytes`` over its groups) over the bytes the call requires
(``bench/bytes.py``): 1 is a plan that reads each input and writes each
output once."""


def read(facts):
    call = facts.get("call")
    if not call:
        return None
    return sum(i["traffic_bytes"] for i in call["impls"]) / call["required_bytes"]
