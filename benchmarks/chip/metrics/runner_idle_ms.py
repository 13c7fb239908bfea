"""Device idle inside the program's runner: per traced study, the ms in
which the host was inside ``engine.run`` (span ``repro.run``) or
``sweep.run_grid`` (span ``repro.grid``) and the chip ran no program,
chip mean; the median over the traced studies (device trace)."""

SPANS = ("repro.run", "repro.grid")


def read(record):
    return None if record.trace is None else record.trace.idle_ms(SPANS)
