"""Host seconds of ``FusionCompiler.compile`` (trace, search, codegen),
before the first call compiles anything for the device."""


def read(facts):
    return facts.get("plan_s")
