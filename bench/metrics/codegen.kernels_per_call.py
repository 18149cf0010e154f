"""Device operations started in the traced window over calls made: the
kernels codegen emits plus whatever XLA adds around them."""


def read(facts):
    call, trace = facts.get("call"), facts.get("trace")
    if not call or not trace:
        return None
    return trace["ops"] / call["calls"]
