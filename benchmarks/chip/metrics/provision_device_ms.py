"""Device time of the provisioning pass: per traced study, the ms of the
leaf operations under the named scope ``provision`` (``engine.step``'s
VM placement, ``vmap(provision)`` in a grid), the busiest chip; the
median over the traced studies (device trace)."""

SCOPE = "provision"


def read(record):
    return None if record.trace is None else record.trace.scope_ms(SCOPE)
