"""Scenario build in set-up: the deployment kind's generator
(``make_mix``) and ``System``, which builds the scenario and puts it on
the chips (host clock)."""


def read(record):
    return record.build_s
