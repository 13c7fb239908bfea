"""Device idle inside the program's summary: per traced study, the ms in
which the host was inside ``sweep.summarize_batch`` (span
``repro.summarize``) and the chip ran no program, chip mean; the median
over the traced studies (device trace)."""

SPANS = ("repro.summarize",)


def read(record):
    return None if record.trace is None else record.trace.idle_ms(SPANS)
