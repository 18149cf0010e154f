"""The whole call's share of the chip's peak FLOP/s, in %: operations a
call needs (``bench/refs``) times calls over the traced window's length,
over the published peak."""


def read(facts):
    call, trace = facts.get("call"), facts.get("trace")
    if not call or not trace:
        return None
    rate = call["flops"] * call["calls"] / trace["window_s"]
    return 100.0 * rate / facts["peak"]["flops_per_s"]
