"""The whole call's share of its roofline, in %: the least time the chip
could take for it (the larger of required bytes over peak HBM bandwidth
and operations over peak FLOP/s, ``bench/bytes.py``, ``bench/peaks.json``)
over the device's busy time per call, busy being the union of every
device operation's interval in the traced window."""
from bench import bytes as req


def read(facts):
    call, trace = facts.get("call"), facts.get("trace")
    if not call or not trace or not trace["busy_s"]:
        return None
    least, _ = req.roofline_s(call["required_bytes"], call["flops"],
                              facts["peak"])
    return 100.0 * least / (trace["busy_s"] / call["calls"])
