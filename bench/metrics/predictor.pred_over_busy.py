"""The predictor's time for the plan (the sum of ``Impl.t_pred``) over
the device's busy time per call in the traced window."""


def read(facts):
    call, trace = facts.get("call"), facts.get("trace")
    if not call or not trace or not trace["busy_s"]:
        return None
    busy_per_call = trace["busy_s"] / call["calls"]
    return sum(i["t_pred"] for i in call["impls"]) / busy_per_call
