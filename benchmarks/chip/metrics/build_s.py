"""Scenario build in set-up: the generator, ``state.make_*``,
``sweep.stack_scenarios`` and the transfer to the chip (host clock)."""


def read(record):
    return record.build_s
