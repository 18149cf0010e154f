"""The routed-expert kernels' share of their roofline, in %: the call's
least time on the chip (``bench/bytes.roofline_s`` on its required bytes
and operations) over the device seconds a call spends in the operations
of ``breakdown.device_ops`` whose name carries ``moe_`` (the group labels
of ``MOE_EXPERTS``'s kernels).  ``call_roofline`` also counts XLA's
operations around them; this share does not.  None where no such
operation ran."""
from bench import bytes as req


def read(facts):
    call, trace = facts.get("call"), facts.get("trace")
    if not call or not trace:
        return None
    moe_s = sum(t for name, t in trace["breakdown"]["device_ops"]
                if "moe_" in name)
    if not moe_s:
        return None
    least, _ = req.roofline_s(call["required_bytes"], call["flops"],
                              facts["peak"])
    return 100.0 * least / (moe_s / call["calls"])
